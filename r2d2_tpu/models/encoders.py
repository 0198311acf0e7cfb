"""Observation encoders.

All encoders take NHWC uint8-normalized float input (channels-last is the
TPU-native conv layout — no NCHW transpose before the MXU) and emit a flat
latent of `latent_dim` features.

- NatureEncoder: the Nature-DQN trunk used by the reference
  (reference model.py:47-57): Conv 32x8x8/4 -> 64x4x4/2 -> 64x3x3/1 ->
  Dense(512), ReLU, VALID padding. 84x84x1 -> 7x7x64 = 3136 -> 512.
  Its first conv reads a frame in blocks of its stride (`frame_block`,
  below).
- ImpalaEncoder: the IMPALA-ResNet stack (Espeholt et al. 2018) for the
  Procgen preset (BASELINE.json config 4).
- MLPEncoder: tiny trunk for unit tests.

Two growth/parallelism dials shared by every trunk (ISSUE 16):

depth    (config.encoder_depth) extra Dense(latent)+relu layers appended
         after the latent projection — auto-named Dense_1, Dense_2, ...
         by nn.compact, which the sharding table leaves REPLICATED (only
         Dense_0 has a column-parallel rule), so deeper trunks need no
         new sharding rules. depth=0 is the historical trunk, bit-exact.
tp_size  manual tensor parallelism (learner.make_manual_train_step's
         shard_map): > 1 builds the SHARD-LOCAL trunk — the latent
         Dense_0 goes column-parallel (features = latent/tp, matching
         the table's contiguous column slices; its bias shards with the
         output axis) and the latent is re-gathered over `tp_axis` after
         the relu (elementwise, so relu-then-gather == gather-then-relu
         bit-exactly). Convs stay replicated, exactly as the table says.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp


def frame_block(encoder: str, obs_shape) -> int:
    """The block `s` in which `encoder`'s first conv reads a frame, or 1.

    A `k x k` conv at stride `s` with `k = m s`, VALID padding and `s`
    dividing H and W is, to a frame of (H, W, C), an `m x m` stride-1 conv
    over (H/s, W/s) blocks of `s*s*C` values: same products, same sums. An
    encoder whose first conv is of that kind publishes `s` as its class's
    `frame_block`; `config.resolved_frame_block` asks here, once, and the
    device stores keep each frame's bytes in that block order
    (replay/block.frames_to_rows), so the step programs hand the conv its
    input by a reshape of the stored rows. 1 = frames as they are."""
    return _block_of(ENCODERS[encoder].frame_block, obs_shape)


def _block_of(stride: int, obs_shape) -> int:
    fits = len(obs_shape) == 3 and obs_shape[0] % stride == 0 and obs_shape[1] % stride == 0
    return stride if fits else 1


def blocked_shape(obs_shape, block: int) -> tuple:
    """(H, W, C) -> (H/s, W/s, s*s*C): a frame's shape in block order."""
    if block == 1:
        return tuple(obs_shape)
    H, W, C = obs_shape
    return (H // block, W // block, block * block * C)


def block_frames(frames, obs_shape, block: int):
    """(..., H, W, C) -> (..., H/s, W/s, s*s*C): channel (dy*s + dx)*C + c of
    block (i, j) is pixel (i*s + dy, j*s + dx, c). numpy or jax."""
    if block == 1:
        return frames
    H, W, C = obs_shape
    n = frames.ndim - 3
    lead = frames.shape[:n]
    x = frames.reshape(*lead, H // block, block, W // block, block, C)
    x = x.transpose(*range(n), n, n + 2, n + 1, n + 3, n + 4)
    return x.reshape(*lead, *blocked_shape(obs_shape, block))


def unblock_frames(blocked, obs_shape, block: int):
    """The inverse of block_frames."""
    if block == 1:
        return blocked
    H, W, C = obs_shape
    n = blocked.ndim - 3
    lead = blocked.shape[:n]
    x = blocked.reshape(*lead, H // block, W // block, block, block, C)
    x = x.transpose(*range(n), n, n + 2, n + 1, n + 3, n + 4)
    return x.reshape(*lead, H, W, C)


class BlockedConv(nn.Module):
    """`nn.Conv(features, (k, k), strides=(s, s), padding="VALID")` with the
    same parameters (`kernel` (k, k, C, features), `bias`), run on frames in
    block order: the (m, m, s*s*C, features) kernel of `frame_block`'s rule
    is re-indexed from the stored one inside the graph, so the gradient
    reaches the parameter as it is. A frame arrives either canonical, (H, W,
    C), and is blocked here, or as the device stores keep it, (H/s, W/s,
    s*s*C); the trailing shape tells them apart. Where `s` does not divide
    H or W the block is 1 and this is the plain strided conv."""

    features: int
    kernel_size: int
    stride: int
    in_shape: tuple  # (H, W, C) of a canonical frame
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        k, s = self.kernel_size, self.stride
        assert k % s == 0, (k, s)
        C, b = self.in_shape[-1], _block_of(s, self.in_shape)
        kernel = self.param("kernel", nn.initializers.lecun_normal(), (k, k, C, self.features))
        bias = self.param("bias", nn.initializers.zeros_init(), (self.features,))
        if x.shape[1:] == tuple(self.in_shape):
            x = block_frames(x, self.in_shape, b)
        elif x.shape[1:] != blocked_shape(self.in_shape, b):
            raise ValueError(
                f"frames {x.shape[1:]} are neither {tuple(self.in_shape)} nor "
                f"its block order {blocked_shape(self.in_shape, b)}"
            )
        m = k // b
        kernel = kernel.reshape(m, b, m, b, C, self.features).transpose(0, 2, 1, 3, 4, 5)
        kernel = kernel.reshape(m, m, b * b * C, self.features)
        y = jax.lax.conv_general_dilated(
            x.astype(self.dtype), kernel.astype(self.dtype), (s // b, s // b), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return y + bias.astype(self.dtype)


def _latent_tail(x, latent_dim, dtype, depth, tp_size, tp_axis):
    """Shared latent projection: column-parallel Dense_0 (+gather under
    tp), then `depth` replicated Dense(latent)+relu layers."""
    x = nn.relu(nn.Dense(latent_dim // tp_size, dtype=dtype)(x))
    if tp_size > 1:
        x = jax.lax.all_gather(x, tp_axis, axis=x.ndim - 1, tiled=True)
    for _ in range(depth):
        x = nn.relu(nn.Dense(latent_dim, dtype=dtype)(x))
    return x


class NatureEncoder(nn.Module):
    latent_dim: int = 512
    dtype: jnp.dtype = jnp.float32
    depth: int = 0
    tp_size: int = 1
    tp_axis: str = "tp"
    # a canonical frame's (H, W, C); None: whatever arrives is canonical.
    # With it the encoder also takes frames already in block order
    obs_shape: Optional[Tuple[int, ...]] = None

    frame_block = 4  # the first conv's stride (frame_block above)

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = x.astype(self.dtype)
        frame = tuple(self.obs_shape or x.shape[1:])
        # the parameter names are nn.Conv's own auto-names: the tree is the
        # one checkpoints, the sharding table and the benchmark's reference read
        conv1 = BlockedConv(32, 8, self.frame_block, frame, dtype=self.dtype, name="Conv_0")
        x = nn.relu(conv1(x))
        x = nn.relu(nn.Conv(64, (4, 4), strides=(2, 2), padding="VALID", dtype=self.dtype, name="Conv_1")(x))
        x = nn.relu(nn.Conv(64, (3, 3), strides=(1, 1), padding="VALID", dtype=self.dtype, name="Conv_2")(x))
        x = x.reshape((x.shape[0], -1))
        return _latent_tail(
            x, self.latent_dim, self.dtype, self.depth, self.tp_size, self.tp_axis
        )


class ResidualBlock(nn.Module):
    channels: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        y = nn.relu(x)
        y = nn.Conv(self.channels, (3, 3), padding="SAME", dtype=self.dtype)(y)
        y = nn.relu(y)
        y = nn.Conv(self.channels, (3, 3), padding="SAME", dtype=self.dtype)(y)
        return x + y


class ImpalaEncoder(nn.Module):
    latent_dim: int = 512
    channels: Sequence[int] = (16, 32, 32)
    dtype: jnp.dtype = jnp.float32
    depth: int = 0
    tp_size: int = 1
    tp_axis: str = "tp"

    frame_block = 1

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = x.astype(self.dtype)
        for ch in self.channels:
            x = nn.Conv(ch, (3, 3), padding="SAME", dtype=self.dtype)(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
            x = ResidualBlock(ch, dtype=self.dtype)(x)
            x = ResidualBlock(ch, dtype=self.dtype)(x)
        x = nn.relu(x)
        x = x.reshape((x.shape[0], -1))
        return _latent_tail(
            x, self.latent_dim, self.dtype, self.depth, self.tp_size, self.tp_axis
        )


class MLPEncoder(nn.Module):
    latent_dim: int = 32
    dtype: jnp.dtype = jnp.float32
    depth: int = 0
    tp_size: int = 1
    tp_axis: str = "tp"

    frame_block = 1

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = x.astype(self.dtype).reshape((x.shape[0], -1))
        return _latent_tail(
            x, self.latent_dim, self.dtype, self.depth, self.tp_size, self.tp_axis
        )


def make_encoder(
    name: str,
    latent_dim: int,
    dtype,
    impala_channels=(16, 32, 32),
    depth: int = 0,
    tp_size: int = 1,
    tp_axis: str = "tp",
    obs_shape=None,
):
    if tp_size > 1 and latent_dim % tp_size != 0:
        raise ValueError(
            f"latent_dim={latent_dim} must divide by tp_size={tp_size} "
            "(column-parallel latent projection)"
        )
    kw = dict(
        latent_dim=latent_dim, dtype=dtype, depth=depth,
        tp_size=tp_size, tp_axis=tp_axis,
    )
    if name == "nature":
        return NatureEncoder(obs_shape=tuple(obs_shape) if obs_shape else None, **kw)
    if name == "impala":
        return ImpalaEncoder(**kw)
    if name == "mlp":
        return MLPEncoder(**kw)
    raise ValueError(f"unknown encoder {name!r}")


ENCODERS = {"nature": NatureEncoder, "impala": ImpalaEncoder, "mlp": MLPEncoder}
