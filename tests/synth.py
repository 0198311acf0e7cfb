"""Synthetic replay contents for the store, snapshot and plane tests (and
`__graft_entry__.dryrun_multichip`): blocks without stepping an env."""

import numpy as np

from r2d2_tpu.replay.block import Block


def synth_block(cfg, rng: np.random.Generator) -> Block:
    """A steady-state mid-episode block (burn-in carried, full length),
    built vectorized — replay-path realistic without stepping envs."""
    B, L, n, S = cfg.burn_in_steps, cfg.learning_steps, cfg.forward_steps, cfg.seqs_per_block
    size = cfg.block_length
    stored = B + size + 1
    forward = np.full(S, n, np.int32)
    forward[-1] = 1  # last sequence of a block cut bootstraps at +1
    return Block(
        obs=rng.integers(0, 255, size=(stored, *cfg.obs_shape), dtype=np.uint8),
        last_action=rng.integers(0, cfg.action_dim, size=stored).astype(np.uint8),
        last_reward=rng.normal(size=stored).astype(np.float32),
        action=rng.integers(0, cfg.action_dim, size=size).astype(np.uint8),
        n_step_reward=rng.normal(size=size).astype(np.float32),
        gamma=np.full(size, cfg.gamma**n, np.float32),
        hidden=(rng.normal(size=(S, 2, cfg.hidden_dim)) * 0.1).astype(np.float32),
        num_sequences=S,
        burn_in_steps=np.full(S, B, np.int32),
        learning_steps=np.full(S, L, np.int32),
        forward_steps=forward,
    )
