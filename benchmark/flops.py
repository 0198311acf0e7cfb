"""Operations and bytes the algorithm needs, computed from shapes alone.

Copied from runs/measure_mfu.py's analytic functions (sound arithmetic, peak
as a default argument) and extended with the per-update total and the Pallas
LSTM kernels' operations and bytes. Multiply-accumulates count twice; the
elementwise recurrence, activations and the optimizer are left out (they are
bandwidth, not MXU work), so a utilization built on these is a lower bound on
what the chip executes and exactly what the algorithm requires.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Tuple

_PEAKS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def device_peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks for `device_kind`; an unknown device is an error."""
    with open(_PEAKS_PATH) as fh:
        table = json.load(fh)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json; add "
            "its published peaks with their source instead of assuming one"
        )
    return table[device_kind]


def nature_encoder_flops_per_frame(obs_shape=(84, 84, 1), latent: int = 512) -> int:
    """Nature-DQN trunk at VALID padding: conv 32x8x8/4, 64x4x4/2, 64x3x3/1,
    dense `latent` (models/encoders.py NatureEncoder)."""
    h, w, cin = obs_shape
    total = 0
    for k, s, cout in ((8, 4, 32), (4, 2, 64), (3, 1, 64)):
        h = (h - k) // s + 1
        w = (w - k) // s + 1
        total += h * w * cout * (k * k * cin) * 2
        cin = cout
    return total + h * w * cin * latent * 2


def mlp_encoder_flops_per_frame(obs_shape, latent: int) -> int:
    n = 1
    for d in obs_shape:
        n *= d
    return 2 * n * latent


def encoder_flops_per_frame(encoder: str, obs_shape, latent: int) -> int:
    if encoder == "nature":
        return nature_encoder_flops_per_frame(tuple(obs_shape), latent)
    if encoder == "mlp":
        return mlp_encoder_flops_per_frame(tuple(obs_shape), latent)
    raise KeyError(f"no FLOP function for encoder {encoder!r} (add one to flops.py)")


def core_flops_per_step(core: str, hidden: int, action_dim: int, lru_chunk: int = 0) -> int:
    """Matmul work of one recurrent step on one row. The core's input is
    concat(latent, one-hot action, reward)."""
    d = hidden + action_dim + 1
    if core == "lru":
        # in_re/in_im (D,H) + out_re/out_im (H,H) + skip (D,H)
        f = 2 * (2 * d * hidden + 2 * hidden * hidden + d * hidden)
        if lru_chunk > 0:
            f += 2 * 4 * lru_chunk * hidden
        return f
    if core == "lstm":
        return 2 * (d + hidden) * 4 * hidden  # wi (D,4H) + wh (H,4H)
    raise KeyError(f"no FLOP function for core {core!r}")


def heads_flops_per_step(hidden: int, action_dim: int) -> int:
    """Dueling heads: two Dense(H) + Dense(A) + Dense(1)."""
    return 2 * (2 * hidden * hidden + hidden * action_dim + hidden)


def update_flops(
    *, encoder: str, obs_shape, hidden: int, action_dim: int, core: str,
    lru_chunk: int, batch: int, burn_in: int, learning: int, forward: int,
) -> int:
    """Model FLOPs one learner update requires (the MFU numerator).

    Online net: forward over all T = burn_in + learning + forward frames,
    backward (2x forward) over the `learning` frames the loss reads. Target
    net: forward over T. Heads: online Q at the learning and the bootstrap
    positions (2L forward, backward through the L learning ones), target Q at
    the L bootstrap positions. Burn-in and the target net are forward only;
    anything the program recomputes is not counted."""
    T = burn_in + learning + forward
    trunk = encoder_flops_per_frame(encoder, obs_shape, hidden) + core_flops_per_step(
        core, hidden, action_dim, lru_chunk
    )
    heads = heads_flops_per_step(hidden, action_dim)
    per_seq = trunk * (T + 2 * learning + T) + heads * (2 * learning + 2 * learning + learning)
    return batch * per_seq


def lstm_fwd_kernel_cost(T: int, B: int, H: int, itemsize: int) -> Tuple[int, int]:
    """(flops, bytes) of ops/pallas_lstm.py `_lstm_fwd_call`: per step
    z = proj[t] + h @ wh. Reads proj (T,B,4H), wh once, h0/c0; writes the h
    sequence (compute dtype) and the c sequence (f32)."""
    flops = T * 2 * B * H * 4 * H
    nbytes = (
        T * B * 4 * H * itemsize + H * 4 * H * itemsize + 2 * B * H * 4
        + T * B * H * itemsize + T * B * H * 4
    )
    return flops, nbytes


def lstm_seq_bwd_kernel_cost(T: int, B: int, H: int, itemsize: int) -> Tuple[int, int]:
    """(flops, bytes) of `_lstm_seq_bwd_call` (the default arm): per step the
    gates are recomputed (h_prev @ wh) and dh = dz @ wh^T. Reads dout (f32),
    proj, h_prev (compute dtype), c_prev and c (f32), wh once; writes dz
    (T,B,4H) f32. The recompute is part of the kernel's own definition (it
    stores no gate residuals), so it is counted here, unlike in update_flops."""
    flops = T * 2 * (2 * B * H * 4 * H)
    nbytes = (
        T * B * H * 4 + T * B * 4 * H * itemsize + T * B * H * itemsize
        + 2 * T * B * H * 4 + H * 4 * H * itemsize + B * H * 4 + T * B * 4 * H * 4
    )
    return flops, nbytes


def roofline_seconds(flops: int, nbytes: int, peaks: Dict[str, float]) -> Tuple[float, str]:
    """Least time the chip could take for (flops, bytes), and which bound."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def lstm_kernels_roofline_seconds_per_update(
    T: int, B: int, H: int, itemsize: int, peaks: Dict[str, float]
) -> Tuple[float, str]:
    """One update runs the forward kernel twice (online, target) and the
    sequence backward once; returns the summed roofline time and the bound of
    the largest term."""
    fwd, fb = roofline_seconds(*lstm_fwd_kernel_cost(T, B, H, itemsize), peaks)
    bwd, bb = roofline_seconds(*lstm_seq_bwd_kernel_cost(T, B, H, itemsize), peaks)
    return 2 * fwd + bwd, (bb if bwd >= 2 * fwd else fb)
