"""Model layer (L2): flax networks compiled by XLA for the TPU MXU.

The reference's Network (reference model.py:35-188) exposes three forwards:
single-step acting, full-sequence target Q, and burn-in+learning Q. Here one
flax module exposes `act` (batched single step) and `unroll` (lax.scan over
the padded fixed-length sequence) — and `unroll` returns BOTH gather views
(learning-window Q and bootstrap-window Q) from a single LSTM pass, because
they differ only in output indexing. That collapses the reference's
3 conv + 3 LSTM evaluations per update to 2 + 2.

Two recurrent core families behind one seam (models/core.py: each core
states what it stores in replay and whether it cuts the gradient at
burn-in): `LSTM` (reference parity, sequential scan / fused Pallas unroll)
and `LRU` (time-parallel diagonal linear recurrence via associative_scan —
models/lru.py).
"""

from r2d2_tpu.models.encoders import ImpalaEncoder, MLPEncoder, NatureEncoder
from r2d2_tpu.models.lru import LRU
from r2d2_tpu.models.lstm import LSTM
from r2d2_tpu.models.r2d2 import R2D2Network

__all__ = [
    "NatureEncoder", "ImpalaEncoder", "MLPEncoder", "LSTM", "LRU",
    "R2D2Network",
]
