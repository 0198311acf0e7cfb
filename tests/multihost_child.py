"""Shared driver for the multi-host replay tests.

`build_and_run(mesh)` fills a MultiHostShardedReplay with per-shard
deterministic blocks and runs 3 collective K=1 dispatches and 2 of K=2 —
called BOTH by the
in-process single-host reference (4 fake devices, all shards local) and by
the real 2-process children this file spawns as `python multihost_child.py
<pid> <nprocs> <port>`. Identical per-shard content + layout-independent
draw seeds mean the two topologies must produce the same losses.
"""

import json
import sys


def _seed_replay(replay, cfg):
    """Fill with per-GLOBAL-shard deterministic blocks: the same blocks
    land in the same shards regardless of how shards are spread over
    processes. Equal priorities -> IS weights exactly 1.0."""
    import numpy as np

    from synth import synth_block

    rngs = {g: np.random.default_rng(100 + g) for g in replay.local_ids}
    for _ in range(2):
        for g in replay.local_ids:
            block = synth_block(cfg, rngs[g])
            prios = np.full(cfg.seqs_per_block, 1.0, np.float32)
            replay.add_block(block, prios, None)
    assert replay.can_sample()


def _allgather_sum(x):
    """Sum a host-local float over all processes (identity single-host)."""
    import jax
    import numpy as np

    x = np.float64(x)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        x = multihost_utils.process_allgather(x).sum()
    return float(x)


def build_and_run(mesh):
    import jax
    import numpy as np

    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.learner import init_train_state, make_sharded_fused_multi_train_step
    from r2d2_tpu.parallel.mesh import replicated_sharding
    from r2d2_tpu.replay.multihost_store import MultiHostShardedReplay

    cfg = tiny_test().replace(batch_size=8)
    replay = MultiHostShardedReplay(cfg, mesh, seed=5)
    _seed_replay(replay, cfg)

    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    state = jax.device_put(state, replicated_sharding(mesh))
    step_fn = make_sharded_fused_multi_train_step(
        cfg, net, mesh, 1, donate=False, is_from_priorities=True
    )
    losses = []
    for _ in range(3):
        state, metrics = replay.run_step_k(step_fn, state, 1)
        losses.append(float(metrics["loss"]))
    # then two K=2 collective scan dispatches (each dispatch also drains
    # the one before's deferred priorities, whatever its K), then the final
    # drain — the full run_step_k lifecycle on both process topologies
    multi_fn = make_sharded_fused_multi_train_step(
        cfg, net, mesh, 2, donate=False, is_from_priorities=True
    )
    for _ in range(2):
        state, metrics = replay.run_step_k(multi_fn, state, 2)
        losses.append(float(metrics["loss"]))
    replay.drain_pending()
    checksum = float(
        sum(np.abs(np.asarray(x)).sum() for x in jax.tree.leaves(state.params))
    )
    # the trees saw every drained priority batch: fold the GLOBAL tree
    # mass into the cross-topology comparison too (each process only
    # holds its local shards' trees)
    checksum += _allgather_sum(
        sum(replay.shards[g].tree.total for g in replay.local_ids)
    )
    return losses, checksum


def build_elastic(mesh, shared_dir, phase):
    """Elastic-resume driver, both sides of a topology change.

    phase="save": seed the replay, run 3 collective steps, drain the
    deferred priorities, snapshot (per-process file + topology manifest +
    the replicated train state as layout-free carry extras), then run 3
    MORE steps and return their losses — the uninterrupted run's
    continuation, the reference a resumed run must reproduce.

    phase="resume": fresh replay on THIS mesh (any process layout),
    reshard_replay over whatever snapshot files the old layout left,
    rebuild the train state from the carry extras, run 3 steps. Because
    the logical shard set (dp=4) is unchanged and draw streams are keyed
    by (seed, GLOBAL shard id, epoch), the losses must be bit-identical
    to the save phase's continuation — across 2proc->1proc, 1proc->2proc,
    or any other regrouping of the same shards."""
    import os

    import jax
    import numpy as np

    from r2d2_tpu.config import tiny_test
    from r2d2_tpu.learner import init_train_state, make_sharded_fused_multi_train_step
    from r2d2_tpu.parallel.mesh import replicated_sharding
    from r2d2_tpu.replay.multihost_store import MultiHostShardedReplay
    from r2d2_tpu.replay.reshard import reshard_replay, snapshot_paths
    from r2d2_tpu.replay.snapshot import save_replay

    cfg = tiny_test().replace(batch_size=8)
    replay = MultiHostShardedReplay(cfg, mesh, seed=5)
    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    treedef = jax.tree.structure(state)
    state = jax.device_put(state, replicated_sharding(mesh))
    step_fn = make_sharded_fused_multi_train_step(
        cfg, net, mesh, 1, donate=False, is_from_priorities=True
    )

    if phase == "save":
        _seed_replay(replay, cfg)
        for _ in range(3):
            state, _ = replay.run_step_k(step_fn, state, 1)
        replay.drain_pending()  # snapshot post-drain: no pending write-backs lost
        extra = {
            f"st_{j}": np.asarray(v) for j, v in enumerate(jax.tree.leaves(state))
        }
        path = os.path.join(
            shared_dir, f"replay_snapshot_p{jax.process_index()}.npz"
        )
        save_replay(replay, path, extra=extra)
    else:
        extras = reshard_replay(replay, snapshot_paths(shared_dir))
        n_leaves = sum(1 for k in extras if k.startswith("st_"))
        state = jax.tree.unflatten(treedef, [extras[f"st_{j}"] for j in range(n_leaves)])
        state = jax.device_put(state, replicated_sharding(mesh))

    losses = []
    for _ in range(3):
        state, metrics = replay.run_step_k(step_fn, state, 1)
        losses.append(float(metrics["loss"]))
    replay.drain_pending()  # before the trees are read
    checksum = float(
        sum(np.abs(np.asarray(x)).sum() for x in jax.tree.leaves(state.params))
    )
    checksum += _allgather_sum(
        sum(replay.shards[g].tree.total for g in replay.local_ids)
    )
    return losses, checksum


def fused_cfg():
    from r2d2_tpu.config import tiny_test

    # sized so the deferred-drain guard holds on a dp=4 mesh: E_local=2,
    # blocks_per_shard=32 >> the 6-slot aliasing bound; episodes (10)
    # fit one collection chunk (block_length=16)
    return tiny_test().replace(
        env_name="catch",
        action_dim=3,
        replay_plane="multihost",
        collector="device",
        num_actors=8,
        batch_size=8,
        updates_per_dispatch=2,
        block_length=16,
        buffer_capacity=16 * 16 * 8,
        learning_starts=64,
        max_episode_steps=10,
        training_steps=8,
    )


def build_and_run_fused(mesh):
    """MultiHostFusedRunner end to end: seed the replay with per-GLOBAL-
    shard deterministic blocks (so the first draws exist), then drive 4
    collective megastep dispatches — K=2 updates + a collection chunk +
    local slab writes each — through the runner's deferred-drain
    protocol, and finish(). Collection is layout-independent by
    construction (env slots and PRNG streams are keyed by GLOBAL shard
    id, draws by (seed, shard, epoch)), so the single-process 4-device
    run and the real 2-process run must produce identical losses, env
    accounting, and tree mass. This pins the runner's HOST-side per-
    process plumbing — slot reservation, addressable-piece chunk drain,
    stamped priority drain — which the single-process tests cannot
    distinguish from global reads."""
    import jax
    import numpy as np

    from r2d2_tpu.megastep import MultiHostFusedRunner
    from r2d2_tpu.envs.catch import CatchEnv
    from r2d2_tpu.learner import init_train_state
    from r2d2_tpu.ops.epsilon import epsilon_ladder
    from r2d2_tpu.parallel.mesh import replicated_sharding
    from r2d2_tpu.replay.multihost_store import MultiHostShardedReplay

    cfg = fused_cfg()
    replay = MultiHostShardedReplay(cfg, mesh, seed=5)
    _seed_replay(replay, cfg)

    net, state = init_train_state(cfg, jax.random.PRNGKey(0))
    state = jax.device_put(state, replicated_sharding(mesh))
    fn_env = CatchEnv(height=cfg.obs_shape[0], width=cfg.obs_shape[1])
    runner = MultiHostFusedRunner(
        cfg, net, fn_env, replay,
        epsilon_ladder(cfg.num_actors), jax.random.PRNGKey(42), mesh,
        collect_every=1, sample_rng=np.random.default_rng(7),
    )
    losses, recorded_total = [], 0
    for _ in range(4):
        state, m, recorded = runner.step(state)
        losses.append(float(m["loss"]))
        recorded_total += recorded
    recorded_total += runner.finish()

    checksum = float(
        sum(np.abs(np.asarray(x)).sum() for x in jax.tree.leaves(state.params))
    )
    # fold in the per-process-visible accounting: local tree mass and the
    # env steps this host recorded into its shards (allgathered so both
    # topologies compare the same global quantity)
    checksum += _allgather_sum(
        sum(replay.shards[g].tree.total for g in replay.local_ids)
    )
    return losses, checksum, _allgather_sum(recorded_total)


def main():
    import os

    pid, nprocs, port = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    mode = sys.argv[4] if len(sys.argv) > 4 else "basic"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo_root)
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(f"localhost:{port}", nprocs, pid)
    assert jax.process_count() == nprocs, jax.process_count()

    from r2d2_tpu.parallel.multihost import make_global_mesh

    mesh = make_global_mesh(tp=1)
    if mode == "fused":
        losses, checksum, steps = build_and_run_fused(mesh)
        payload = {"pid": pid, "losses": losses, "checksum": checksum,
                   "env_steps": steps}
    elif mode in ("elastic_save", "elastic_resume"):
        shared_dir = sys.argv[5]
        losses, checksum = build_elastic(
            mesh, shared_dir, "save" if mode == "elastic_save" else "resume"
        )
        payload = {"pid": pid, "losses": losses, "checksum": checksum}
    else:
        losses, checksum = build_and_run(mesh)
        payload = {"pid": pid, "losses": losses, "checksum": checksum}
    print("CHILD_RESULT " + json.dumps(payload), flush=True)


if __name__ == "__main__":
    main()
