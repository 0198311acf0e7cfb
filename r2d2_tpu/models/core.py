"""The recurrent core's seam: what a core stores in replay, and how.

A recurrent core (models/lstm.py `LSTM`, models/lru.py `LRU`) is a flax
module registered by name in `config.RECURRENT_CORES`. Besides
`__call__(xs, carry, burn_in=None)` and `step(x, carry)` its class states
three things, and every other module asks the class rather than knowing:

- `state_shape(cfg)`: the per-sequence shape of its STORED state, `(n, H)`
  (today `(2, cfg.hidden_dim)` for both cores); the stored dtype is
  `cfg.state_dtype`. THE RULE: the carry is a tuple of `n` arrays `(B, H)`,
  float32 between steps, and the stored array is those rows stacked on
  axis 1, `(B, n, H)`. `pack_state` / `unpack_state` are the one pair that
  converts; `zero_state` / `zero_carry` are the episode-start state in
  either form (reference worker.py:502). Replay stores, collectors, the
  accumulator and the analysis entry points are built from these
  (`replay/block.store_field_specs(cfg)["hidden"]` is `state_spec`), so a
  core with another `n` changes its own `state_shape` and nothing else. A
  core whose state is not rows of H needs this rule widened first.
- `from_config(cfg, in_dim, tp_size)`: builds the module; a backend or a
  backward arm is resolved there, once, by the config's own rules.
- `cuts_at_burn_in`: whether `__call__` cuts the gradient at each row's
  `burn_in` (then `R2D2Network.unroll` differentiates the encoder from that
  seam only). A core without a seam ignores the argument.

`serve/state_cache.py` and `liveloop/tap.py` still hold the state as two
arrays of H (ROADMAP D1b); `check_two_row_state` makes them refuse any
other core where they are built.
"""

from __future__ import annotations

import importlib
from typing import Tuple

import jax.numpy as jnp
import numpy as np

from r2d2_tpu.config import RECURRENT_CORES

Carry = Tuple[jnp.ndarray, ...]  # state_shape(cfg)[0] arrays, each (B, H)


def core_class(cfg):
    """The class registered for `cfg.recurrent_core`."""
    entry = RECURRENT_CORES[cfg.recurrent_core]
    if isinstance(entry, str):
        module, _, name = entry.partition(":")
        entry = getattr(importlib.import_module(module), name)
    return entry


def state_spec(cfg):
    """(shape, dtype) of one sequence's stored state."""
    return tuple(core_class(cfg).state_shape(cfg)), cfg.state_dtype


def zero_state(cfg, *lead) -> np.ndarray:
    """The episode-start state as the accumulator packs it: `(*lead, n, H)`
    float32 zeros (numpy); the stores downcast at write time."""
    return np.zeros((*lead, *state_spec(cfg)[0]), np.float32)


def zero_carry(cfg, batch: int) -> Carry:
    """The episode-start carry for `batch` rows, float32."""
    n, *row = state_spec(cfg)[0]
    return tuple(jnp.zeros((batch, *row), jnp.float32) for _ in range(n))


def pack_state(carry):
    """Carry, a tuple of n `(B, H)` arrays -> stored `(B, n, H)`. numpy in,
    numpy out; anything else goes through jax.numpy (traceable). The dtype
    is the carry's: a writer casts to the store's."""
    xp = np if all(isinstance(x, np.ndarray) for x in carry) else jnp
    return xp.stack(carry, axis=1)


def unpack_state(stored) -> Carry:
    """Stored `(B, n, H)` -> carry: the inverse of pack_state."""
    return tuple(stored[:, i] for i in range(stored.shape[1]))


def check_two_row_state(state_shape, hidden_dim: int, core: str, who: str) -> None:
    """For the holders that keep the state as an `h` and a `c` array of H:
    refuse, where they are built, a core that stores anything else."""
    if tuple(state_shape) != (2, hidden_dim):
        raise ValueError(
            f"{who} keeps the recurrent state as two arrays of "
            f"hidden_dim={hidden_dim} (stored shape (2, {hidden_dim})); core "
            f"{core!r} stores {tuple(state_shape)} (ROADMAP D1b)"
        )
