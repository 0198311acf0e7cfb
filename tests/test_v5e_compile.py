"""Compiles for a described (not attached) TPU v5e: what the chip's compiler
makes of the Pallas LSTM and LRU kernels at the benchmark cells' own T, B, H,
with no chip time. Pinned here: every kernel is a Mosaic custom call whose HLO
instruction is named after its jitted wrapper (`pl.pallas_call(name=...)`),
which is what the benchmark's `lstm_kernel` trace pattern anchors on
(`kernels.lstm_ms_per_update`, `kernels.lstm_roofline`: a line without them is
refused).

All such compiles live in THIS file: the process that describes the topology
holds the TPU library until it exits (on-chip-measurement guide, section 2),
and the topology is described inside a fixture, never at import. They need ONE
worker: under pytest-xdist run the file with `--dist loadfile` (the tier-1
command) or `--dist loadgroup` (the `xdist_group` mark below). Under plain
`-n N` the cases land on several workers, only one of which gets libtpu's
lock: the others FAIL and say so, because a skip would let the pin vanish
unnoticed. The only skip is an installation without libtpu."""

import importlib.util
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from r2d2_tpu.ops import pallas_lstm as pk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, H = 85, 512  # nature-lstm512: burn-in 40 + learning 40 + n-step 5, LSTM-512
ROWS = {"one_chip": 64, "dp4_per_chip": 16}

pytestmark = pytest.mark.xdist_group("v5e_compile")


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) in this installation")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.fail(
            f"libtpu is installed but no v5e:2x2 topology can be described: {e}\n"
            "If the error names libtpu's lock file, another process holds the library "
            "(another xdist worker: run this file with --dist loadfile or loadgroup)."
        )


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compiled_kernels(monkeypatch):
    """The kernels as they are built on the chip: not interpreted, VMEM limit
    from the v5e's 128 MiB (the code asks the attached device, a CPU here)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    monkeypatch.setattr(pk, "vmem_capacity_bytes", lambda: 128 << 20)
    # a compile for a described device is written to the persistent cache but
    # cannot be read back without a chip: keep it out
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _lstm_kernel_pattern():
    with open(os.path.join(ROOT, "benchmark", "trace_patterns.json")) as fh:
        return re.compile(json.load(fh)["categories"]["lstm_kernel"])


ARMS = {
    # op -> (op, the wrappers whose kernels its forward + backward launch)
    "plain": (lambda: pk.lstm_unroll, ["_lstm_fwd_call", "_lstm_bwd_call"]),
    "seq_default": (lambda: pk.lstm_seq_unroll, ["_lstm_fwd_call", "_lstm_seq_bwd_call"]),
}


@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("arm", sorted(ARMS))
def test_every_kernel_instruction_matches_the_benchmarks_lstm_pattern(arm, rows, one_chip, compiled_kernels):
    B = ROWS[rows]
    op, wrappers = ARMS[arm][0](), ARMS[arm][1]
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = [sds((T, B, 4 * H), jnp.bfloat16), sds((H, 4 * H), jnp.bfloat16),
            sds((B, H), jnp.float32), sds((B, H), jnp.float32)]
    if arm != "plain":
        args.append(sds((B,), jnp.int32))

    def loss(proj, wh, h0, c0, *burn):
        outs, (hT, cT) = op(proj, wh, h0, c0, *burn)
        return outs.astype(jnp.float32).sum() + hT.sum() + cT.sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*args).compile().as_text()
    calls = [l.strip() for l in text.splitlines() if 'custom_call_target="tpu_custom_call"' in l]
    names = [re.sub(r"^ROOT ", "", l).split(" = ")[0] for l in calls]
    pattern = _lstm_kernel_pattern()
    assert len(calls) == 2 and all(pattern.search(re.sub(r"^ROOT ", "", l)) for l in calls), names
    # named after the wrapper, so a pattern can tell the calls apart
    assert sorted(re.sub(r"^%|\.\d+$", "", n) for n in names) == sorted(wrappers)


def _lstm_cfg(T_, B, H_):
    """The `atari` preset at another training shape, on the Pallas backend:
    T = 85 is its own window (burn-in 40 + learning 40 + n-step 5), T = 581
    bench.py::long_context_main's (64 + 512 + 5, blocks of 1,024)."""
    from r2d2_tpu.config import default_atari

    burn, learning, block = {85: (40, 40, 400), 581: (64, 512, 1024)}[T_]
    return default_atari().replace(
        lstm_backend="pallas", batch_size=B, hidden_dim=H_, burn_in_steps=burn,
        learning_steps=learning, forward_steps=5, block_length=block,
        buffer_capacity=256 * block)


@pytest.fixture()
def as_on_the_chip(monkeypatch, compiled_kernels):
    """What LSTM.from_config observes on a v5e: a TPU backend (the VMEM size is
    compiled_kernels')."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


# the two shapes at which backward_arm="auto" left the one backward that is
# kept (PR 37): for its 128 MB dial on HBM residuals, never for VMEM
@pytest.mark.parametrize("shape", [(581, 32, 512), (85, 256, 512)], ids=lambda s: "x".join(map(str, s)))
def test_the_sequence_backward_compiles_within_vmem_where_auto_used_to_leave_it(shape, one_chip, as_on_the_chip):
    from r2d2_tpu.models.lstm import LSTM

    T_, B, H_ = shape
    cfg = _lstm_cfg(T_, B, H_)
    assert (cfg.seq_len, cfg._rows_per_device(), cfg.hidden_dim) == shape
    LSTM.from_config(cfg, in_dim=H_ + cfg.action_dim + 1)  # the core resolves: not refused
    sds = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)
    args = [sds((T_, B, 4 * H_), jnp.bfloat16), sds((H_, 4 * H_), jnp.bfloat16),
            sds((B, H_), jnp.float32), sds((B, H_), jnp.float32), sds((B,), jnp.int32)]

    def loss(proj, wh, h0, c0, burn):
        outs, (hT, cT) = pk.lstm_seq_unroll(proj, wh, h0, c0, burn)
        return outs.astype(jnp.float32).sum() + hT.sum() + cT.sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*args).compile()
    # it compiled under the limit the call itself passed (`vmem_limit_bytes` =
    # this estimate: Mosaic refuses a kernel that needs more than it was given)
    need = pk.kernel_vmem_bytes(
        *pk._seq_bwd_vmem_spec(B, H_, jnp.float32, *[jnp.bfloat16] * 3), B, H_, jnp.bfloat16)
    assert need <= 128 << 20 and need >> 20 == {(581, 32, 512): 15, (85, 256, 512): 46}[shape]
    calls = [l for l in compiled.as_text().splitlines() if 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == 2
    # what the one backward keeps in HBM here: the float32 dz (PERF.md finding 37)
    dz = T_ * B * 4 * H_ * 4
    assert dz == {(581, 32, 512): 152_305_664, (85, 256, 512): 178_257_920}[shape]
    assert compiled.memory_analysis().temp_size_in_bytes >= dz


def test_a_shape_over_vmem_is_refused_where_the_core_is_resolved_with_its_shape(as_on_the_chip):
    """H = 2,048 at B = 64 fits no backward this kernel ever had (~196 MiB of
    128): the configuration is refused when the core is built from it, by
    shape, and not at the first trace or inside Mosaic."""
    from r2d2_tpu.models.lstm import LSTM

    cfg = _lstm_cfg(85, 64, 2048)
    with pytest.raises(ValueError, match=r"T=85, B=64, H=2048 \(bfloat16\).*128 MiB"):
        LSTM.from_config(cfg, in_dim=2048 + cfg.action_dim + 1)
    LSTM.from_config(_lstm_cfg(85, 64, 1024), in_dim=1024 + cfg.action_dim + 1)  # 58 MiB: fits


@pytest.mark.parametrize("config", ["nature-lstm512", "lru-seq581"])
def test_store_gather_and_slab_write_read_the_obs_store_in_place(config, one_chip, compiled_kernels):
    """The replay half of both step programs at a benchmark configuration's
    real store shape: K-free gather of one batch (PR 41: the frames by one
    clipped index each, the scalar fields as windows of B rows), then the
    donated slab write of one collected chunk. With frames stored as
    lane-aligned rows (replay/block.frames_to_rows) the chip's compiler reads
    and writes the 4 GB obs store in place; with raw (84, 84, 1) frames it
    re-laid the whole store out on every dispatch (PERF.md finding 1: 6.4 GB
    of temp)."""
    from benchmark import harness
    from r2d2_tpu import learner, megastep
    from r2d2_tpu.replay.block import store_field_specs
    from r2d2_tpu.utils import profiling

    conf = harness.load_json(os.path.join(ROOT, "benchmark", "configs", config + ".json"))
    cfg = harness.build_config(conf, 1)
    E, B = cfg.num_actors, cfg.batch_size
    sds = lambda shape, dt: jax.ShapeDtypeStruct(tuple(shape), dt, sharding=one_chip)
    specs = store_field_specs(cfg)
    stores = {k: sds((cfg.num_blocks, *shape), dt) for k, (shape, dt) in specs.items()}
    chunk = {k: sds((E, *shape), dt) for k, (shape, dt) in specs.items()}
    gather = learner.make_store_gather(cfg)

    def replay_half(stores, chunk, ptr0, b, s, w):
        batch = gather(stores, b, s, w)
        return megastep._slab_write(stores, chunk, ptr0), batch

    compiled = jax.jit(replay_half, donate_argnums=(0,)).lower(
        stores, chunk, sds((), jnp.int32), sds((B,), jnp.int32), sds((B,), jnp.int32), sds((B,), jnp.float32)
    ).compile()
    obs_store_bytes = cfg.num_blocks * math.prod(specs["obs"][0])
    assert obs_store_bytes > 3.9e9  # the benchmark's real size, not a toy
    text = compiled.as_text()
    assert profiling.relayouts_at_least(text, obs_store_bytes // 2) == []
    # the obs gather indexes ONE flattened (block * slot) axis: the two-index
    # form of the same gather compiles too, and halts the v5e's core when it
    # runs (PERF.md finding 25.2; learner.make_store_gather says the same).
    # Nothing else reads the obs store: no dynamic-slice of it (B windows copied
    # by a loop ran at half the gather's pace, finding 41.1) and no loop at all
    lines = [l.strip() for l in text.splitlines()]
    obs_gathers = [l for l in lines if re.search(r"= u8\[[\d,]+,128\]\S* gather\(", l)]
    assert obs_gathers and all("collapsed_slice_dims={0}," in l for l in obs_gathers), obs_gathers
    assert not [l for l in lines if re.search(r"= u8\[[\d,]+\]\S* dynamic-slice\(", l)]
    assert not [l for l in lines if re.search(r" while\(", l)]
    # no second pass over the uint8 batch: jnp.take's default mode was a
    # `select` against the fill value over u8[B,T,7056] (PR 41 took it out);
    # nor a pad or a concatenate that assembles the batch from parts
    passes = [l for l in lines if re.search(r"= u8\[[\d,]+\]\S* (select|pad|concatenate)\(", l)]
    assert passes == [], passes
    # the five per-step scalar fields are B row reads (learner._windows), not
    # B x T or B x L single entries (~9 ns an index on the chip, finding 41)
    T_, L_ = cfg.seq_len, cfg.learning_steps
    shapes = [re.search(r"= [sf]32\[([\d,]+)\]\S* gather\(", l) for l in lines]
    shapes = [tuple(map(int, m.group(1).split(","))) for m in shapes if m]
    assert not {(B, T_), (B, L_)} & set(shapes), shapes
    assert shapes.count((B, cfg.block_slot_len)) == 2 and shapes.count((B, cfg.block_length)) == 3, shapes
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= obs_store_bytes  # the store is updated in place
    assert memory.temp_size_in_bytes < 2.5e9, memory.temp_size_in_bytes  # slab and batch only


def _encoder_backward_leading_dims(text):
    """{conv layer: leading dimensions} over every instruction of a compiled
    program whose op_name sits in the encoder's BACKWARD (`transpose(jvp` ...
    `enc/Conv_n`): its own result shapes and its operands', arrays of rank 3
    and more (activations, cotangents and filters; the compiled text prints an
    operand by name, so shapes are looked up where the operand is defined)."""
    shape_rx = re.compile(r"\b[a-z]+\d*\[([\d,]+)\]")
    shapes, flagged = {}, []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = ", line)
        if not m:
            continue
        body = line[m.end():].split(", metadata=")[0]
        cut = re.search(r"\s[a-z][\w\-]*\((?=%|\))", body)  # `fusion(%a, ...`: where the operands start
        head, operands = (body[:cut.start()], body[cut.start():]) if cut else (body, "")
        shapes[m.group(1)] = [tuple(map(int, s.split(","))) for s in shape_rx.findall(head)]
        op = re.search(r'op_name="([^"]*)"', line)
        hit = op and re.search(r"transpose\(jvp.*enc/Conv_(\d)", op.group(1))
        if hit:
            flagged.append((int(hit.group(1)), [m.group(1), *re.findall(r"%[\w.\-]+", operands)]))
    dims = {}
    for conv, names in flagged:
        dims.setdefault(conv, set()).update(s[0] for n in names for s in shapes.get(n, []) if len(s) >= 3)
    return dims


def _compiled_unroll_gradient(cfg, rows, one_chip):
    """-> (compiled text, seconds) of `unroll` under value_and_grad at `rows`
    sequences of the configuration's T, fed frames AS STORED (PR 38: the
    step programs' form, `(B, T, 21, 21, 16)` under the Nature trunk on
    84x84x1), for the described chip."""
    import time

    from r2d2_tpu.models.encoders import blocked_shape
    from r2d2_tpu.models.r2d2 import R2D2Network, init_params

    if cfg.recurrent_core == "lstm":
        cfg = cfg.replace(lstm_backend="pallas")  # "auto" asks the attached device, a CPU here
    net = R2D2Network.from_config(cfg)
    B, seq = rows, cfg.seq_len
    sds = lambda shape, dt: jax.ShapeDtypeStruct(tuple(shape), dt, sharding=one_chip)
    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(lambda k: init_params(k, cfg)[1], jax.random.PRNGKey(0)))
    stored = blocked_shape(cfg.obs_shape, cfg.resolved_frame_block)
    batch = [sds((B, seq, *stored), jnp.uint8), sds((B, seq), jnp.int32), sds((B, seq), jnp.float32),
             sds((B, 2, cfg.hidden_dim), jnp.float32), sds((B,), jnp.int32), sds((B,), jnp.int32), sds((B,), jnp.int32)]

    def loss(p, *b):
        q_learn, q_boot, mask = net.apply(p, *b)
        return jnp.sum(q_learn * mask[..., None]) + jnp.sum(q_boot)

    t = time.time()
    text = jax.jit(jax.value_and_grad(loss)).lower(params, *batch).compile().as_text()
    return text, time.time() - t


def _cell_config(config):
    from benchmark import harness

    return harness.build_config(harness.load_json(os.path.join(ROOT, "benchmark", "configs", config + ".json")), 1)


@pytest.mark.parametrize("config,rows,differentiated", [
    ("nature-lstm512", 64, "window"), ("nature-lstm512-dp4", 16, "window"), ("lru-seq581", 32, "sequence")])
def test_encoder_backward_runs_over_the_frames_that_can_receive_a_gradient(
        config, rows, differentiated, one_chip, compiled_kernels):
    """`unroll` under value_and_grad at a cell's own rows per chip and T:
    behind the LSTM's burn-in seam every backward instruction of the three
    convs has B*(L+F) frames (each row's from its seam) and none has B*T; the
    LRU core publishes no seam and its encoder backward keeps B*T. (With B*L
    frames, a multiple of 128 at 16, 32 and 48 rows, the chip's compiler took
    five minutes and more over conv1's backward-filter: PERF.md finding 32.3.)

    Fed frames as stored (PR 38), the program holds no `[N,84,84,1]` array at
    all: conv1 reads `(N, 21, 21, 16)` by a reshape of the rows, and its
    backward-filter is the 2x2/1 conv's `f32[2,2,16,32]`, which the graph
    re-indexes to the `(8, 8, 1, 32)` parameter's gradient."""
    cfg = _cell_config(config)
    assert cfg.resolved_frame_block == 4
    B, seq, W = rows, cfg.seq_len, cfg.learning_steps + cfg.forward_steps
    text, _ = _compiled_unroll_gradient(cfg, rows, one_chip)
    dims = _encoder_backward_leading_dims(text)
    frames = {"window": B * W, "sequence": B * seq}[differentiated]
    assert sorted(dims) == [0, 1, 2], dims
    for conv, got in dims.items():
        filters = {d for d in got if d <= 8}  # 2x2 (conv1 over blocks), 4x4, 3x3 kernels
        assert got - filters == {frames}, (conv, got)
    assert not re.search(r"\[\d+,84,84,1\]", text), re.findall(r"\S+\[\d+,84,84,1\]\S*", text)[:5]
    conv1_backward = [l for l in text.splitlines() if re.search(r"transpose\(jvp.*enc/Conv_0", l)]
    assert any(re.search(r"= f32\[2,2,16,32\]", l) for l in conv1_backward)
    assert not any(re.search(r"= f32\[8,8,1,32\]\S* (fusion|convolution)\(", l) and "kind=kOutput" in l
                   for l in conv1_backward)


@pytest.mark.parametrize("rows", [16, 32, 48, 64])
def test_conv1s_blocked_backward_filter_compiles_in_seconds_at_every_row_count(rows, one_chip, compiled_kernels):
    """PERF.md finding 32.3's hazard, asked of the 2x2/1 conv's emitter: with
    rows x 45 frames behind the LSTM's seam (720, 1,440, 2,160, 2,880; the
    8x8/4 conv's backward-filter took minutes at some frame counts), the whole
    `unroll` gradient compiles in well under two minutes."""
    cfg = _cell_config("nature-lstm512")
    text, seconds = _compiled_unroll_gradient(cfg, rows, one_chip)
    assert re.search(r"= f32\[2,2,16,32\]", text)
    assert seconds < 120, seconds


def _rehearsal():
    spec = importlib.util.spec_from_file_location(
        "rehearse_step_programs", os.path.join(ROOT, "runs", "rehearse_step_programs.py"))
    rehearse = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rehearse)
    return rehearse


_PROGRAMS = {}


def _step_program(config, topo, which):
    """(cfg, compiled text) of one of a configuration's two step programs at
    real size, as the chip builds it: compiled once for every test of this
    file that reads it (under `compiled_kernels`, which each of them asks for)."""
    if (config, which) not in _PROGRAMS:
        cfg, programs, _ = _rehearsal().step_programs(config, topo)
        fn, args = programs[which]
        _PROGRAMS[config, which] = cfg, fn.lower(*args).compile().as_text()
    return _PROGRAMS[config, which]


def _multi_program(config, topo):
    """The update-only step program (`jit_multi`; dp4: the shard_map body, 16
    rows a chip)."""
    return _step_program(config, topo, "multi")


def _top_level(text):
    """Of a compiled program's text: (name, dtype, dims, opcode, op_name) of
    every instruction with an array result outside the fusions' bodies: what
    the program writes to memory. A bitcast moves nothing and is left out."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    inside = None
    for line in text.splitlines():
        head = re.match(r"\s*(?:ENTRY )?(%[\w.\-]+) \(.*\{\s*$", line)
        if head:
            inside = head.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (\w+)\[([\d,]+)\]\S* ([a-z][\w\-]*)\(", line)
        if not m or inside in fused or m.group(4) in ("bitcast", "parameter", "get-tuple-element"):
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        yield m.group(1), m.group(2), tuple(map(int, m.group(3).split(","))), m.group(4), op.group(1) if op else ""


def _hand_over_passes(text, B, T, frame_bytes):
    """Of a compiled step program's text: every `u8[B,T,bytes]` shape in it
    (the batch's bytes tiled over (T, bytes)), and every instruction between
    the store gather and conv1 (op_name under `r2d2_gather` or `_core_input`,
    not under `enc/`), outside the fusions' bodies, that writes a bf16 array of
    B x T x frame_bytes elements or more: a pass over the whole batch at two
    bytes."""
    tiled = sorted(set(re.findall(rf"\bu8\[{B},{T},\d+\]", text)))
    passes = [name for name, dtype, dims, _, op in _top_level(text)
              if dtype == "bf16" and math.prod(dims) >= B * T * frame_bytes
              and re.search(r"jit\(r2d2_gather\)|_core_input", op) and "/enc/" not in op]
    return tiled, passes


@pytest.mark.parametrize("config", ["nature-lstm512", "lru-seq581", "nature-lstm512-dp4"])
def test_the_batch_reaches_conv1_with_the_frame_index_as_one_axis(config, topo, compiled_kernels):
    """A configuration's update-only step program (`jit_multi`; dp4: the
    shard_map body, 16 rows a chip) at real size: `replay/block.rows_as_stored`
    turns the gathered rows into bytes with (B, T) merged, so no `u8[B,T,7168]`
    or `u8[B,T,7056]` exists (flattened under (B, T) the chip tiled the batch
    over (T, bytes) and every cell paid a pass for it), and between the
    `r2d2_gather` gather and `enc/Conv_0` the only instruction that writes the
    whole batch in bf16 is the one convert (lru; behind the LSTM's seam the
    encoder reads two sub-batches and nothing is that large). PR 43's parent
    failed this in lru (`copy bf16[32,581,7056]` and `reshape
    bf16[18592,21,21,16]`, 1.87 ms of 21.7 an update) and in both nature cells
    (`u8[64,85,7168]`, `u8[16,85,7168]`). The mechanism engages in every gather
    or in none, so this text is its tripwire where a counter would read 100 %."""
    from r2d2_tpu.replay.block import LANES, obs_rows

    cfg, text = _multi_program(config, topo)
    B, T, n = cfg._rows_per_device(), cfg.seq_len, math.prod(cfg.obs_shape)
    assert (B, T, n, obs_rows(cfg.obs_shape) * LANES) == (
        {"nature-lstm512": 64, "lru-seq581": 32, "nature-lstm512-dp4": 16}[config],
        {"lru-seq581": 581}.get(config, 85), 7056, 7168)
    assert re.search(rf"= u8\[{B * T},56,128\]\S* fusion\(", text)  # the gather, by its merged index
    tiled, passes = _hand_over_passes(text, B, T, n)
    assert tiled == [], tiled
    if cfg.recurrent_core == "lru":  # no seam: the whole batch goes to conv1 in one call
        assert len(passes) == 1 and "convert" in passes[0], passes
    else:
        assert passes == [], passes


@pytest.mark.parametrize("config", ["nature-lstm512", "lru-seq581", "nature-lstm512-dp4"])
def test_behind_the_seam_conv1_is_handed_bytes_and_no_bf16_copy_of_a_part_is_written(config, topo, compiled_kernels):
    """The same program's text. Behind the LSTM's seam `_core_input` gathers
    each row's window (B x (L + F) frames) and its other frames (B x (T - L -
    F)) once for both nets, and hands each part to conv1 as bytes in frame
    shape behind an `optimization_barrier`: the program writes no bf16 array
    the size of a part (PR 50's parent wrote `convert_multiply_fusion
    bf16[2880,7056]` and `bf16[2560,7056]`, re-laid the first out at two
    bytes an entry and had every conv1 read those copies: 0.14 of nature's
    3.96 ms an update, PERF.md finding 50), each part is re-laid-out for the
    conv as `u8`, and the convert sits inside the conv fusions, which take a
    `u8[N,21,21,16]` operand. There is still ONE gather of each part. (That
    the others' bytes are released only with the window's encoding shows in
    where the core's arrays live: the `S(1)` pins of the next test.) The
    no-seam path is untouched: lru keeps its one `convert_multiply_fusion
    bf16[18592,7056]` (there the barrier would part the 7,168 -> 7,056 slice
    from the convert: a pass of its own, finding 50.1). The mechanism engages
    in every update or in none, so this text is its tripwire."""
    cfg, text = _multi_program(config, topo)
    B, T, W, n = cfg._rows_per_device(), cfg.seq_len, cfg.learning_steps + cfg.forward_steps, math.prod(cfg.obs_shape)
    written = list(_top_level(text))
    if cfg.recurrent_core == "lru":
        converts = [name for name, dtype, dims, _, _ in written if (dtype, dims) == ("bf16", (B * T, n))]
        assert len(converts) == 1 and "convert_multiply_fusion" in converts[0], converts
        return
    parts = {"window": B * W, "others": B * (T - W)}
    assert parts == {"nature-lstm512": {"window": 2880, "others": 2560},
                     "nature-lstm512-dp4": {"window": 720, "others": 640}}[config]
    for part, N in parts.items():
        copies = [(name, dims) for name, dtype, dims, _, op in written
                  if dtype == "bf16" and math.prod(dims) == N * n and "_core_input" in op]
        assert copies == [], (part, copies)
        gathers = [name for name, dtype, dims, opcode, op in written
                   if (dtype, dims, opcode) == ("u8", (N, n), "fusion") and op.endswith("_core_input/jit(_take)/gather")]
        assert len(gathers) == 1, (part, gathers)
        # the conv fusions' own signatures: a part's bytes go in, in frame shape
        convs = re.findall(rf"^%fused_computation\S* \(([^)]*u8\[{N},21,21,16\][^)]*)\) -> \(?(?:bf16|f32|u32)\[", text, re.M)
        assert any("bf16[2,2,16,32]" in operands for operands in convs), (part, convs)  # with conv1's blocked kernel


@pytest.mark.parametrize("config", ["nature-lstm512", "lru-seq581", "nature-lstm512-dp4"])
def test_the_tail_of_unroll_and_the_loss_hold_no_index_per_row_and_step(config, topo, compiled_kernels):
    """The same program's text: each row's learning and bootstrap positions
    are ONE window of the core's outputs, moved by a selection matmul
    (`R2D2Network._dueling_window`), and the loss picks Q by action with a
    select over A (`learner._q_at`). So no instruction carries the op_name of
    `unroll`'s own `take_along_axis` or of the loss's, no `scatter-add` is
    named under `unroll` outside `_core_input` and the core (whose frame
    gathers and slices keep theirs), and nothing H wide is scattered at
    all (the scatter's own root carried no op_name: a trace booked it
    unscoped, 0.043 / 0.365 / 0.046 ms an update, PERF.md finding 46). PR 46's
    parent failed the first in all three. The mechanism engages in every
    update or in none, so this text is its tripwire where a counter would
    read 100 %."""
    cfg, text = _multi_program(config, topo)
    names = sorted(set(re.findall(r'op_name="([^"]*)"', text)))  # inside the fusions' bodies too
    assert any("R2D2Network.unroll/R2D2Network._dueling_window/" in n for n in names)
    assert any("jit(r2d2_loss)" in n for n in names)
    indexed = [n for n in names if "R2D2Network.unroll/jit(take_along_axis)" in n
               or re.search(r"jit\(r2d2_loss\)\)*/jit\(take_along_axis\)", n)]
    assert indexed == [], indexed[:3]
    scattered = [n for n in names if "scatter-add" in n and "R2D2Network.unroll/" in n
                 and "_core_input" not in n and "R2D2Network.unroll/core/" not in n]
    assert scattered == [], scattered[:3]
    assert not re.search(rf"= \w+\[[\d,]*{cfg.hidden_dim}\]\S* scatter\(", text)
    # since PR 49 the seam's way back to time order holds none either
    # (`r2d2._time_order`: static slices, a select and the same kind of band;
    # its `take_along_axis` of B x T rows of 516 and the unnamed scatter-add
    # of its transpose were 0.27 ms of nature's 4.28 an update, PERF.md
    # finding 49): nothing under `_core_input` is indexed row by row but the
    # frames, and nothing as wide as the core's input is scattered
    band = [n for n in names if "_core_input/btj,bjd->btd/" in n]
    if cfg.recurrent_core == "lstm":
        assert any("transpose(jvp(" in n for n in band) and any("transpose(jvp(" not in n for n in band)
        assert not any("_core_input/jit(take_along_axis)" in n for n in names)
        assert not any("scatter-add" in n and "_core_input" in n for n in names)
        width = cfg.hidden_dim + cfg.action_dim + 1  # (the latent's width is asserted above)
        assert not re.search(rf"= \w+\[[\d,]*{width}\]\S* scatter\(", text)
    else:  # no seam: the one call, and none of the band's names
        assert band == [], band[:3]
    if config == "nature-lstm512":
        # what the first band form of PR 49 lost, with every predicted op gone:
        # the compiler made the core's input again for the backward, and the
        # core's own arrays lost their place in the chip's fast memory (`S(1)`
        # in a layout): the update was 3.4 % SLOWER. `_core_input` keeps its
        # result behind an `optimization_barrier`; a later change that evicts
        # these fails here, on the CPU, where PR 49 needed a traced pair.
        # (PR 50's first form did: both parts' bytes released to conv1 at
        # once, the target's projection out of `S(1)`, the core +0.084 ms
        # on an encoder 0.10 faster, the rate -0.04 %. Released one after
        # the other the same bytes leave both projections where they were
        # and the rate reads +3.1 %: PERF.md finding 50.2)
        backward = re.findall(r"^\s*%_lstm_seq_bwd_call[.\d]* = (\S+) custom-call\(", text, re.M)
        assert len(backward) == 1 and "S(1)" in backward[0], backward
        projections = re.findall(
            r"= (bf16\[5440,2048\]\S*) fusion\([^\n]*R2D2Network\.unroll/core/dot_general", text)
        assert len(projections) == 2 and all("S(1)" in p for p in projections), projections


def test_lru_kernels_compile_at_the_cells_shape_named_after_their_wrappers(one_chip, compiled_kernels):
    """ops/pallas_lru.py at lru-seq581's own (T, B, H) = (581, 32, 512):
    forward and its VJP are two Mosaic custom calls (the chip's compiler
    refuses a block that is not whole tiles or asks for more VMEM than it
    was given), named after their jitted wrappers, in 7 chunks of 83 steps
    with no padding."""
    from r2d2_tpu.ops import pallas_lru

    T, B, H = 581, 32, 512
    assert pallas_lru.chunk_len(T, B) == 83
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def loss(*args):
        h_re, h_im = pallas_lru.lru_scan(*args)
        return jnp.sum(h_re * h_im) + jnp.sum(h_re[-1])

    text = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        sds(H), sds(H), sds(T, B, H), sds(T, B, H), sds(B, H), sds(B, H)).compile().as_text()
    calls = [l.strip() for l in text.splitlines() if 'custom_call_target="tpu_custom_call"' in l]
    names = sorted(re.sub(r"^%|\.\d+$", "", re.sub(r"^ROOT ", "", l).split(" = ")[0]) for l in calls)
    assert names == ["_lru_fwd_call", "_lru_rev_call"], names
    assert not re.search(r"f32\[(58[2-9]|59\d|6\d\d),32,512\]", text)  # no padded copy of u or h


def test_lru_step_program_keeps_the_recurrence_in_three_kernel_calls(topo, compiled_kernels):
    """lru-seq581's update-only step program (`jit_multi`) at real size with
    the core the chip builds: the online forward, the target forward and the
    reversed pass are Mosaic calls under `core/..._scan_states` (what
    `model.lru_recurrence_ms_per_update` anchors on), and the core is a few
    hundred instructions where the associative scan made 3,399 of it."""
    from r2d2_tpu.utils import profiling

    _, text = _multi_program("lru-seq581", topo)
    op_names = profiling.parse_op_names(text)
    kernels = {k: v for k, v in op_names.items() if re.match(r"%?_lru_(fwd|rev)_call", k)}
    assert sorted(re.sub(r"^%|\.\d+$", "", k) for k in kernels) == ["_lru_fwd_call", "_lru_fwd_call", "_lru_rev_call"]
    assert all("R2D2Network.unroll/core" in v and "_scan_states" in v for v in kernels.values()), kernels
    assert _rehearsal().instructions_in_buckets(text)["core"] < 600


def _computations(text):
    """{computation's name: its instruction lines} of a compiled program's text."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"\s*(?:ENTRY )?(%[\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.strip() == "}":
            name = None
        elif name:
            comps[name].append(line)
    return comps


def _called_from(comps, root):
    """`root` and every computation it calls, directly or not (fusions, nested loops)."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in comps and name not in seen:
            seen.add(name)
            for line in comps[name]:
                todo += re.findall(r"(?:calls|body|condition|to_apply)=(%[\w.\-]+)", line)
    return seen


def test_the_stacks_collect_scans_carry_the_layers_parts_and_no_flat_row(topo, compiled_kernels):
    """nemotron-twotower-30b-a3b-ep16's collecting step program (`jit_mega`) at
    published widths: the two scans of a chunk's env steps (segments of 448 and
    576, the `while`s of the `r2d2_collect` scope) carry the stack's state as
    its layers' parts (`models/core.py`, the opened form): the three mixers'
    `f32[16,64,64,128]` and the attention's keys and values are loop-carried
    buffers of their own, and nothing inside a body, its fusions included, has
    the flat row's `[16,2152576]` or its unpadded `[16,2152450]`. PR 54's parent
    failed this with six `fusion f32[16,2152450]` and a `pad f32[16,2152576]` in
    each body (138 MB written twice more at every one of 1,024 env steps: 7.8 %
    of the device by the ledger's op list, PERF.md finding 54). The mechanism
    engages in every chunk or in none, so this text is its tripwire."""
    cfg, text = _step_program("nemotron-twotower-30b-a3b-ep16", topo, "mega")
    comps = _computations(text)
    scans = [line for lines in comps.values() for line in lines
             if " while(" in line and 'op_name="jit(mega)/jit(r2d2_collect)/while"' in line]
    assert len(scans) == 2, len(scans)
    E = cfg.num_actors
    for line in scans:
        carried = line.split(" while(")[0]
        assert len(re.findall(rf"f32\[{E},64,64,128\]", carried)) == 3, carried[:400]
        assert len(re.findall(rf"f32\[{E},1024,2,128\]", carried)) == 2, carried[:400]
        body = re.search(r"body=(%[\w.\-]+)", line).group(1)
        flat = [l.split(", metadata=")[0].strip() for name in _called_from(comps, body) for l in comps[name]
                if re.search(rf"\[{E},(2152450|2152576)\]", l)]
        assert flat == [], flat[:4]


def test_the_mixers_sequence_form_holds_no_array_with_groups_or_heads_in_the_tiles_minor_axes(topo, compiled_kernels):
    """nemotron-twotower-30b-a3b-ep16 at published widths, the K updates of its
    collecting step program (`jit_mega` holds the scan over updates that
    `jit_multi` is, beside the collector; the text is the one the test above
    compiled, so the file pays no second compile of this cell): under the
    mixers' op_names (`/ssm_<i>`) nothing has the grouped norm's `(.., 8, 512)`
    view, and no instruction outside the fusions writes the chunked scan's
    `(B, n, Q, G, R, P)` operands, their `(B, T', H, P)` padded form or the
    `(B, n, Q, G, R)` scalars: the axes that are 8 or 64 long are nowhere a
    big array's minor axis, so no pass exists only to re-tile one. PR 55's
    parent failed this with 24 `f32[8,581,8,512]` rows, 24 + 18 bare
    `reshape` / `copy f32[8,5,128,8,8,64]`, 24 `f32[8,640,64,64]` and a hundred
    of `f32[8,5,128,8,8]` in its traced run (~45 of the mixers' 112.7 ms an
    update, PERF.md finding 55). What is LEFT with a minor axis of 64 is the
    projection's 64-wide `dt` slice itself, 1.3 MB, on its way to `(B, n, H,
    Q)`: pinned by size, not by count. The mechanism is compiled in or it is
    not, so this text is its tripwire."""
    cfg, text = _step_program("nemotron-twotower-30b-a3b-ep16", topo, "mega")
    B, T = cfg.batch_size, cfg.seq_len
    n = -(-T // 128)
    mixers = [(dtype, dims, opcode) for _, dtype, dims, opcode, op in _top_level(text)
              if re.search(r"/ssm_\d+", op) and dims[:1] == (B,)]
    assert len(mixers) > 300, len(mixers)  # the three layers, four times an update
    assert f"f32[{B},{T},8,512]" not in text
    heads_minor = {(B, n, 128, 8, 8, 64), (B, n * 128, 64, 64), (B, n, 128, 8, 8)}
    assert [m for m in mixers if m[1] in heads_minor and m[2] in ("reshape", "copy")] == []
    # time is the minor axis of the chunk operands and of the scalars
    assert any(dims == (B, n, 4096, 128) for _, dims, _ in mixers)
    narrow = [m for m in mixers if m[0] == "f32" and m[1][-1] in (8, 64) and m[2] != "fusion"]
    assert all(math.prod(dims) * 4 <= B * n * 128 * 64 * 4 for _, dims, _ in narrow), narrow[:4]


def _gdn_kernel_pattern():
    with open(os.path.join(ROOT, "benchmark", "layers", "kernels.gdn_solve_ms_per_update.json")) as fh:
        return re.compile(json.load(fh)["pattern"])


def _mosaic_calls(text):
    return [re.sub(r"^ROOT ", "", l.strip()) for l in text.splitlines() if 'custom_call_target="tpu_custom_call"' in l]


def _xlas_triangular_solves(text):
    """The instructions XLA's `solve_triangular` leaves in a compiled text: the
    `InvertDiagBlocksLowerTriangular` custom call itself, and whatever carries
    the primitive's name in its op_name (the matmuls of its expansion)."""
    return [l.strip()[:160] for l in text.splitlines()
            if "InvertDiagBlocksLowerTriangular" in l or re.search(r'op_name="[^"]*triangular_solve', l)]


def test_the_delta_rules_chunk_solve_compiles_at_the_cells_shape_named_after_its_wrapper(one_chip, compiled_kernels):
    """ops/pallas_delta.py at qwen3-next-80b-a3b-ep32's own shape (B 8, n 10,
    Hk 16, R 2: 2,560 triangles of Q = 64; 256 right-hand columns): the solve
    forward + backward holds ONE Mosaic custom call, the inverse, named after
    its jitted wrapper, which `kernels.gdn_solve_ms_per_update`'s own pattern
    finds; the backward pass is matmuls on the saved inverse and launches no
    kernel; and nothing of XLA's triangular solve is left."""
    from r2d2_tpu.ops import pallas_delta

    lead, Q = (8, 10, 16, 2), 64
    assert pallas_delta.kernel_fits(math.prod(lead), Q)
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    loss = lambda L, rhs: jnp.sum(jnp.square(pallas_delta.unit_lower_solve(L, rhs)))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(sds(*lead, Q, Q), sds(*lead, Q, 256)).compile().as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 1 and _gdn_kernel_pattern().search(calls[0]), calls
    assert re.sub(r"^%|\.\d+$", "", calls[0].split(" = ")[0]) == "_gdn_inverse_call"
    assert f"f32[{Q},{Q},{math.prod(lead)}]" in calls[0]            # a triangle a lane: nothing padded
    assert _xlas_triangular_solves(text) == []


def test_the_cells_delta_layer_holds_no_triangular_solve_of_xlas(one_chip, compiled_kernels):
    """One Gated DeltaNet layer of qwen3-next-80b-a3b-ep32 at published widths
    and the cell's B x T, forward + recompute + backward as the step programs
    run it: its chunk solves are the kernel's calls under the recurrence's
    scope, and the compiled text has no `InvertDiagBlocksLowerTriangular`
    (12.9 ms a call on the chip, nine an update: PERF.md finding 57) and no
    `triangular_solve`."""
    from r2d2_tpu.models import hybrid_stack as hs

    cfg = _cell_config("qwen3-next-80b-a3b-ep32")
    sizes = hs.spec_of(cfg).sizes("D")
    B, T = cfg.batch_size, cfg.seq_len
    assert (B, T, sizes.chunk, sizes.key_heads, sizes.value_heads) == (8, 581, 64, 16, 32)
    layer = hs._layer(sizes, jnp.dtype(cfg.resolved_compute_dtype), "D", 1)
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    x, (delta, tail) = sds(B, T, sizes.hidden_size), (sds(B, *s) for s in hs.GatedDeltaNet.state_shapes(sizes))
    params = jax.tree.map(lambda a: sds(*a.shape), jax.eval_shape(layer.init, jax.random.PRNGKey(0), x, delta, tail))
    loss = lambda p, x, delta, tail: jnp.sum(jnp.square(layer.apply(p, x, delta, tail)[0]))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x, delta, tail).compile().as_text()
    calls = _mosaic_calls(text)
    assert calls and all(_gdn_kernel_pattern().search(l) for l in calls), calls
    assert all(re.search(r'op_name="[^"]*gdn_1\.recurrence', l) for l in calls)
    assert _xlas_triangular_solves(text) == []


@pytest.mark.parametrize("config,held,width", [("nemotron-twotower-30b-a3b-ep16", 8, 2688),
                                               ("qwen3-next-80b-a3b-ep32", 16, 2048)])
def test_an_acting_step_of_the_cells_mixture_builds_no_slot_table_and_the_update_still_does(
        config, held, width, one_chip, compiled_kernels):
    """One mixture layer of each stack cell at published widths, forward, as
    the chip's compiler has it. At acting's `num_actors` = 16 tokens, which fit
    a held expert's 128 rows: no scatter, no gather but the K chosen scores
    a token, and no array of `held x 128` rows (the queue's `f32[8,128,2688]`
    / `f32[16,128,2048]`, PERF.md finding 59): the held experts run on the 16
    tokens themselves. At the update's B x T tokens: the queue, as before. The
    form follows the shape, so the compiled text is its engagement record."""
    from r2d2_tpu.models import hybrid_stack as hs

    cfg = _cell_config(config)
    sizes = hs.spec_of(cfg).sizes("E")
    assert (cfg.num_actors, sizes.held, sizes.hidden_size) == (16, held, width)
    layer = hs._layer(sizes, jnp.dtype(cfg.resolved_compute_dtype), "E", 0)
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def text_at(*shape):
        x = sds(*shape, width)
        params = jax.tree.map(lambda a: sds(*a.shape), jax.eval_shape(layer.init, jax.random.PRNGKey(0), x))
        return jax.jit(lambda p, x: layer.apply(p, x)).lower(params, x).compile().as_text()

    gathers = lambda text: re.findall(r"= (\w+\[[\d,]*\])\S* gather\(", text)
    rows = lambda tokens: rf"\[{held},{sizes.capacity(tokens)},{width}\]"
    acting = text_at(cfg.num_actors)
    assert sizes.capacity(cfg.num_actors) == 128
    assert "scatter" not in acting and gathers(acting) == [f"f32[16,{sizes.top_k}]"]
    assert not re.search(rows(cfg.num_actors), acting)
    assert re.search(rf"f32\[{held},16,{width}\]", acting)            # each held expert's output for the 16 tokens
    tokens = cfg.batch_size * cfg.seq_len
    update = text_at(cfg.batch_size, cfg.seq_len)
    assert tokens == 4648 > sizes.capacity(tokens) in (256, 512)
    assert "scatter(" in update and re.search(rows(tokens), update)
    assert f"f32[{held * sizes.capacity(tokens)},{width}]" in gathers(update)


def test_every_prefetch_wait_of_natures_update_program_has_an_owner(topo, compiled_kernels):
    """nature-lstm512's `multi` as the chip builds it: memory-space assignment
    puts in some 280 asynchronous copies and slices, none with an op_name, 3 %
    of the chip's time (`device.prefetch_wait_share`). `profiling.parse_heirs`
    gives the `-done` of each an owner (its consumer; its producer for a write
    back), the scan's `while` walked through. What it leaves is a prefetch
    for the NEXT iteration, carried: its source is an element of the body's
    parameter and the body's root its only consumer."""
    from r2d2_tpu.utils import profiling

    _, text = _multi_program("nature-lstm512", topo)
    named, heirs = profiling.parse_op_names(text), profiling.parse_heirs(text)
    comps = profiling.parse_instructions(text)
    scheduled = {i.name for i in profiling.top_level(comps)}
    dones, unexplained = [], []
    for body in comps.values():
        if body[0].name not in scheduled:
            continue
        by_name = {i.name: i for i in body}
        for i in body:
            if not i.opcode.endswith("-done") or i.name in named:
                continue
            dones.append(i.name)
            if i.name in heirs:
                continue
            users = [j for j in body if i.name in j.operands]
            source = by_name.get((by_name[i.operands[0]].operands or [""])[0])
            carried = (all(j.root and j.opcode == "tuple" for j in users) and source is not None
                       and source.opcode == "get-tuple-element" and by_name[source.operands[0]].opcode == "parameter")
            if not carried:
                unexplained.append(f"{i.name} {i.shape} (consumers {[j.name for j in users]})")
    assert {"copy-done", "slice-done"} <= {re.sub(r"\.\d+$", "", d) for d in dones} and len(dones) > 200
    assert not unexplained, f"`-done` instructions without an heir that are no carried prefetch: {unexplained}"
    owned = [heirs[d] for d in dones if d in heirs]
    assert len(owned) >= len(dones) - 2
    assert sum(how == "waits_for" for _, how in owned) > 0.6 * len(dones)
    # an heir is a name of the program's own work (a device scope, a module path), never an argument's
    assert all("jit(r2d2_" in op or "R2D2Network." in op for op, _ in owned)
