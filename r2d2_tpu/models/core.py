"""The recurrent core's seam: what a core stores in replay, and how.

A recurrent core (models/lstm.py `LSTM`, models/lru.py `LRU`,
models/hybrid_stack.py `HybridStack`) is a flax module registered by name in
`config.RECURRENT_CORES`. Besides `__call__(xs, carry, burn_in=None)` and
`step(x, carry)` its class states three things, and two more where its state
is large, and every other module asks the class rather than knowing:

- `state_shape(cfg)`: the per-sequence shape of its STORED state, `(n, W)`
  (`(2, cfg.hidden_dim)` for the LSTM and the LRU); the stored dtype is
  `cfg.state_dtype`. THE RULE: the carry is a tuple of `n` arrays `(B, W)`,
  float32 between steps, and the stored array is those rows stacked on
  axis 1, `(B, n, W)`. `pack_state` / `unpack_state` are the one pair that
  converts; `zero_state` / `zero_carry` are the episode-start state in
  either form (reference worker.py:502). Replay stores, collectors, the
  accumulator and the analysis entry points are built from these
  (`replay/block.store_field_specs(cfg)["hidden"]` is `state_spec`), so a
  core with another `n` or another width changes its own `state_shape` and
  nothing else. THE `(1, S)` FORM: a core whose state is not rows of H (a
  stack of layers, each with a state of its own shape) states `(1, S)`: ONE
  flat float32 vector a row, which the class itself splits into its layers'
  parts and joins again (`HybridStack`: every mixer's state and convolution
  tail, every attention's keys and values, a count; zero is the episode
  start). The rule did not have to be widened for it.
- `from_config(cfg, in_dim, tp_size)`: builds the module; a backend or a
  backward arm is resolved there, once, by the config's own rules.
- `cuts_at_burn_in`: whether `__call__` cuts the gradient at each row's
  `burn_in` (then `R2D2Network.unroll` differentiates the encoder from that
  seam only). A core without a seam ignores the argument.
- `keeps_window_starts` (optional, default False): the class of a core whose
  state is large says True, and the device collector (collect.py) then keeps
  the carry at a block's static window starts alone (its scan runs in
  segments that end there) where it otherwise stacks the state at every
  step of the chunk: `(T, E, *state_shape)` float32 is 141 GB for the stack
  at published widths. What `_pack` stores is bit for bit the same either
  way (tests/test_hybrid_stack.py). This statement decides it; there is no
  config knob. Why not every core: a segment is a scan of its own, and a
  chunk of the LSTM cells has nine distinct starts (T = 400, L = Bn = 40),
  so their `mega` program grows from 9,997 to 29,228 instructions and its
  compile from 15.6 s to 28.0 s (compiled for the described v5e, PR 53,
  PERF.md finding 53.8) against a bound of a tenth on `setup_s`, to spare
  0.7 GB of temporaries that fit.
- `open_carry` / `close_carry` / `step_open` (optional, all three or none): a
  core whose `step` does not work on its stored form says how a `Carry` is
  OPENED into the form its step carries from one step to the next
  (`HybridStack`: a tuple of its layers' parts, float32), how that form is
  CLOSED back into the `Carry`, and a step on the opened form, with `step ==
  close_carry . step_open . open_carry` bit for bit. The opened form is for
  ONE caller, a loop that steps many times and stores rarely: the device
  collector opens the carry once a chunk, scans its env steps over the
  opened form (`R2D2Network.act_select(..., opened=True)`) and closes where a
  state is stored (a block's window starts, the chunk's end), so a step moves
  what the recurrence needs and not the whole stored row twice more (138 MB
  joined, then padded, at each of 1,024 env steps: PERF.md finding 54). A
  class that states nothing (the LSTM, the LRU) has the identity for both and
  its `step` (`open_carry`, `close_carry`, `step_open` below), so its programs
  are the same text either way. Nothing outside that loop sees the opened
  form: the stores, the gather, `batch["hidden"]`, the snapshot and
  `CollectCarry` hold the `Carry`. No config knob here either.

`serve/state_cache.py` and `liveloop/tap.py` still hold the state as two
arrays of H (ROADMAP D1b); `check_two_row_state` makes them refuse any
other core where they are built, the stack among them.
"""

from __future__ import annotations

import importlib
from typing import Tuple

import jax.numpy as jnp
import numpy as np

from r2d2_tpu.config import RECURRENT_CORES

Carry = Tuple[jnp.ndarray, ...]  # state_shape(cfg)[0] arrays, each (B, state_shape(cfg)[1])


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
    """The episode-start state as the accumulator packs it: `(*lead, n, W)`
    float32 zeros (numpy); the stores downcast at write time."""
    return np.zeros((*lead, *state_spec(cfg)[0]), np.float32)


def zero_carry(cfg, batch: int) -> Carry:
    """The episode-start carry for `batch` rows, float32."""
    n, *row = state_spec(cfg)[0]
    return tuple(jnp.zeros((batch, *row), jnp.float32) for _ in range(n))


def pack_state(carry):
    """Carry, a tuple of n `(B, W)` arrays -> stored `(B, n, W)`. numpy in,
    numpy out; anything else goes through jax.numpy (traceable). The dtype
    is the carry's: a writer casts to the store's."""
    xp = np if all(isinstance(x, np.ndarray) for x in carry) else jnp
    return xp.stack(carry, axis=1)


def unpack_state(stored) -> Carry:
    """Stored `(B, n, W)` -> carry: the inverse of pack_state."""
    return tuple(stored[:, i] for i in range(stored.shape[1]))


def open_carry(core, carry: Carry):
    """`carry` in the form `core`'s step carries between steps: the core's own
    statement, or the carry itself (module docstring)."""
    return core.open_carry(carry) if hasattr(core, "open_carry") else carry


def close_carry(core, opened) -> Carry:
    """The inverse of `open_carry`."""
    return core.close_carry(opened) if hasattr(core, "close_carry") else opened


def step_open(core, x, opened):
    """One step of the bound module `core` on the opened form -> (out, opened)."""
    return getattr(core, "step_open", core.step)(x, opened)


def check_two_row_state(state_shape, hidden_dim: int, core: str, who: str) -> None:
    """For the holders that keep the state as an `h` and a `c` array of H:
    refuse, where they are built, a core that stores anything else."""
    if tuple(state_shape) != (2, hidden_dim):
        raise ValueError(
            f"{who} keeps the recurrent state as two arrays of "
            f"hidden_dim={hidden_dim} (stored shape (2, {hidden_dim})); core "
            f"{core!r} stores {tuple(state_shape)} (ROADMAP D1b)"
        )
