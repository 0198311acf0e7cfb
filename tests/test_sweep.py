"""Sweep driver (r2d2_tpu/sweep.py): config construction for the full
Atari-57 suite, and a tiny end-to-end 2-game sweep on the catch env."""

import json
import os

import pytest

from r2d2_tpu.config import tiny_test
from r2d2_tpu.sweep import ATARI_57, run_sweep, sweep_config


def test_atari_57_is_57_games():
    assert len(ATARI_57) == 57
    assert len(set(ATARI_57)) == 57
    for g in ("MsPacman", "Breakout", "Seaquest", "Qbert", "MontezumaRevenge"):
        assert g in ATARI_57


def test_sweep_configs_validate_for_all_games(tmp_path):
    for game in ATARI_57:
        cfg = sweep_config(game, preset="atari", root=str(tmp_path))
        assert cfg.env_name == game
        assert game in cfg.checkpoint_dir
        assert cfg.metrics_path.endswith("metrics.jsonl")


def test_tiny_two_game_sweep(tmp_path):
    from r2d2_tpu.train import Trainer

    root = str(tmp_path / "sweep")

    def factory(cfg):
        # swap the Atari env for the fast catch env, keep everything else
        cfg = tiny_test().replace(
            env_name="catch",
            training_steps=3,
            checkpoint_dir=cfg.checkpoint_dir,
            metrics_path=cfg.metrics_path,
        )
        return Trainer(cfg)

    rows = run_sweep(
        ["Breakout", "Pong"], root=root, mode="inline", trainer_factory=factory
    )
    assert [r["game"] for r in rows] == ["Breakout", "Pong"]
    for r in rows:
        assert r["steps"] == 3
        assert r["env_steps"] > 0
    with open(os.path.join(root, "summary.jsonl")) as fh:
        lines = [json.loads(l) for l in fh]
    assert len(lines) == 2


def test_cli_rejects_unknown_game():
    from r2d2_tpu.sweep import main

    with pytest.raises(SystemExit):
        main(["--games", "NotAGame"])


def test_cli_allow_any_env_flag(tmp_path):
    from r2d2_tpu.sweep import main

    rows_path = tmp_path / "summary.jsonl"
    main(["--games", "catch", "--preset", "tiny_test", "--root", str(tmp_path),
          "--steps", "4", "--mode", "inline", "--allow-any-env"])
    assert rows_path.exists()


def test_sweep_two_games_distinct_action_dims(tmp_path):
    """Back-to-back games with DIFFERENT action spaces (the Atari-57
    reality: per-game reduced action sets): the driver must rebuild the
    dueling head per game (Trainer auto-corrects action_dim from the env),
    keep checkpoint/metrics dirs separate, and sequence runs cleanly.
    'scripted:A' pins each fake game's action space without ALE."""
    from r2d2_tpu.sweep import run_sweep

    rows = run_sweep(
        ["scripted:4", "scripted:7"],
        preset="tiny_test",
        root=str(tmp_path / "sweep"),
        steps=2,
        mode="inline",
        cfg_overrides=dict(
            learning_starts=32, num_actors=2, buffer_capacity=640,
            save_interval=1,
        ),
    )
    assert [r["game"] for r in rows] == ["scripted:4", "scripted:7"]
    for r in rows:
        assert r["steps"] >= 2 and r["env_steps"] > 0
    # per-game artifacts are isolated
    for g in ("scripted:4", "scripted:7"):
        assert (tmp_path / "sweep" / g / "metrics.jsonl").exists()
        assert (tmp_path / "sweep" / g / "checkpoints").exists()


def test_threaded_host_env_pool_matches_serial():
    """ThreadedHostEnvPool: same step()/reset_all() results as the serial
    pool on deterministic envs, per-env ordering preserved."""
    import numpy as np

    from r2d2_tpu.actor import HostEnvPool, ThreadedHostEnvPool
    from r2d2_tpu.envs.fake import ScriptedEnv

    def mk():
        return [ScriptedEnv(obs_shape=(4, 4, 1), action_dim=3, episode_len=5,
                            rewards=[float(i)] * 5) for i in range(6)]

    serial, threaded = HostEnvPool(mk()), ThreadedHostEnvPool(mk(), workers=3)
    np.testing.assert_array_equal(serial.reset_all(), threaded.reset_all())
    for t in range(7):  # crosses the episode_len=5 auto-reset boundary
        acts = np.arange(6) % 3
        o1, r1, d1, n1 = serial.step(acts)
        o2, r2, d2, n2 = threaded.step(acts)
        np.testing.assert_array_equal(o1, o2)
        np.testing.assert_array_equal(r1, r2)
        np.testing.assert_array_equal(d1, d2)
        np.testing.assert_array_equal(n1, n2)
    # rewards are per-env-identity: ordering held through the pool
    assert list(r2) == [0.0, 1.0, 2.0, 3.0, 4.0, 5.0]
