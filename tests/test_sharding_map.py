"""The data-driven sharding map (parallel/sharding_map.py): wildcard
pattern grammar, exact parity with the retired hardcoded Megatron layout,
the fsdp optimizer-state axis, the quantized serve tree, and the
fsdp-agnostic snapshot topology contract (ISSUE 14 tentpole)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from r2d2_tpu.config import parse_overrides, tiny_test
from r2d2_tpu.learner import init_train_state, make_train_step
from r2d2_tpu.parallel import (
    DEFAULT_RULES,
    make_mesh,
    serve_param_shardings,
    shard_batch,
    train_state_shardings,
)
from r2d2_tpu.parallel.sharding_map import match_axes, process_name, spec_for
from tests.test_learner import random_batch


# suffix -> spec of the OLD hardcoded train_state_shardings (the layout
# every pre-map checkpoint/test was built against); everything else P()
_OLD_LAYOUT = {
    "core.wi": P(None, "tp"),
    "core.wh": P(None, "tp"),
    "core.b": P("tp"),
    "Dense_0.kernel": P(None, "tp"),
    "Dense_0.bias": P("tp"),
    "adv_hidden.kernel": P(None, "tp"),
    "adv_hidden.bias": P("tp"),
    "val_hidden.kernel": P(None, "tp"),
    "val_hidden.bias": P("tp"),
    "adv_out.kernel": P("tp", None),
    "val_out.kernel": P("tp", None),
}


def _old_spec(name: str) -> P:
    for suf, spec in _OLD_LAYOUT.items():
        if name.endswith(suf):
            return spec
    return P()


class TestPatternGrammar:
    def test_process_name_collapses_integers(self):
        import jax.tree_util as jtu

        path = (
            jtu.GetAttrKey("opt_state"),
            jtu.SequenceKey(1),
            jtu.SequenceKey(0),
            jtu.GetAttrKey("mu"),
            jtu.DictKey("params"),
            jtu.DictKey("core"),
            jtu.DictKey("wi"),
        )
        assert process_name(path) == "opt_state.*.*.mu.params.core.wi"

    def test_first_match_wins_scale_before_row_rule(self):
        """The ROW-parallel heads' (1, out) scale must hit its explicit
        replicated entry BEFORE the generic kernel* row rule claims it."""
        assert match_axes("params.adv_out.kernel.scale", DEFAULT_RULES) == ()
        assert match_axes("params.adv_out.kernel.q8", DEFAULT_RULES) == ("tp", None)
        assert match_axes("params.adv_out.kernel", DEFAULT_RULES) == ("tp", None)

    def test_unmatched_names_replicate(self):
        assert match_axes("params.enc.Conv_0.kernel", DEFAULT_RULES) == ()
        assert match_axes("step", DEFAULT_RULES) == ()

    def test_spec_drops_axes_missing_from_mesh(self):
        """A tp rule against a dp-only mesh degrades to replicated, never
        an invalid axis name."""
        mesh = make_mesh(dp=8, tp=1)  # 2-axis but tp size 1 still has "tp"
        leaf = jnp.zeros((16, 64))
        s = spec_for("params.core.wi", leaf, mesh)
        assert s == P(None, "tp")


class TestOldLayoutParity:
    def test_train_state_matches_retired_hardcoded_layout(self):
        """Every leaf of a real TrainState gets EXACTLY the spec the old
        name-set implementation produced — params, target_params, and the
        mu/nu mirrors alike (the drop-in guarantee existing checkpoints
        and the tp planes rely on)."""
        import jax.tree_util as jtu

        cfg = tiny_test()
        _, state = init_train_state(cfg, jax.random.PRNGKey(0))
        mesh = make_mesh(dp=2, tp=2, devices=jax.devices()[:4])
        sh = train_state_shardings(state, mesh)
        for path, s in jtu.tree_flatten_with_path(sh)[0]:
            name = process_name(path)
            assert s.spec == _old_spec(name), (name, s.spec)

    def test_moments_mirror_param_specs(self):
        """Adam mu/nu inherit each param's tp spec through the same
        wildcards — no per-moment rule duplication."""
        import jax.tree_util as jtu

        cfg = tiny_test()
        _, state = init_train_state(cfg, jax.random.PRNGKey(0))
        mesh = make_mesh(dp=2, tp=2, devices=jax.devices()[:4])
        sh = train_state_shardings(state, mesh)
        flat = {process_name(p): s.spec for p, s in jtu.tree_flatten_with_path(sh)[0]}
        for name, spec in flat.items():
            if name.startswith("params."):
                tail = name[len("params."):]
                assert flat[f"opt_state.*.*.mu.{tail}"] == spec
                assert flat[f"opt_state.*.*.nu.{tail}"] == spec


class TestQuantizedServeTree:
    def test_q8_and_scale_leaves_follow_kernel_rules(self):
        """One table drives train AND serve placement: quantize_tree's
        q8 leaf inherits the kernel's Megatron spec, column scales shard
        with their output axis, and the ROW heads' (1, out) scale stays
        replicated (no input dim to shard)."""
        import jax.tree_util as jtu

        from r2d2_tpu.ops.quantize import quantize_tree

        cfg = tiny_test()
        _, state = init_train_state(cfg, jax.random.PRNGKey(0))
        q, n = quantize_tree(state.params)
        assert n > 0
        mesh = make_mesh(dp=2, tp=2, devices=jax.devices()[:4])
        sh = serve_param_shardings(q, mesh)
        flat = {process_name(p): s.spec for p, s in jtu.tree_flatten_with_path(sh)[0]}
        assert flat["params.enc.Dense_0.kernel.q8"] == P(None, "tp")
        assert flat["params.enc.Dense_0.kernel.scale"] == P(None, "tp")
        assert flat["params.adv_out.kernel.q8"] == P("tp", None)
        assert flat["params.adv_out.kernel.scale"] == P()
        assert flat["params.val_out.kernel.scale"] == P()

    def test_server_mesh_publish_places_int8_tree(self):
        """PolicyServer(mesh=...) routes every publish — here the int8
        arm — through serve_param_shardings: the published q8 kernels
        land tp-sharded on the mesh."""
        from r2d2_tpu.serve.server import PolicyServer, ServeConfig

        cfg = tiny_test().replace(serve_quantization="int8")
        mesh = make_mesh(dp=1, tp=2, devices=jax.devices()[:2])
        srv = PolicyServer(cfg, ServeConfig(), mesh=mesh)
        assert srv.quantized_leaves > 0
        pub = srv._published[0]
        q8 = pub["params"]["enc"]["Dense_0"]["kernel"]["q8"]
        assert q8.sharding.spec == P(None, "tp")
        assert len({s.device for s in q8.addressable_shards}) == 2

    def test_server_rejects_device_and_mesh(self):
        from r2d2_tpu.serve.server import PolicyServer, ServeConfig

        with pytest.raises(ValueError, match="not both"):
            PolicyServer(
                tiny_test(), ServeConfig(),
                device=jax.devices()[0],
                mesh=make_mesh(dp=1, tp=2, devices=jax.devices()[:2]),
            )


class TestFsdpAxis:
    def test_mesh_backcompat_and_third_axis(self):
        assert make_mesh(dp=4, tp=2).axis_names == ("dp", "tp")
        m3 = make_mesh(dp=2, tp=2, fsdp=2)
        assert m3.axis_names == ("dp", "tp", "fsdp")
        assert m3.shape["fsdp"] == 2
        with pytest.raises(ValueError, match="devices"):
            make_mesh(dp=3, tp=2, fsdp=2)
        with pytest.raises(ValueError, match="fsdp"):
            make_mesh(dp=8, fsdp=0)

    def test_fsdp_shards_moments_only(self):
        """ZeRO-1 scope: mu/nu leaves gain the fsdp axis on a divisible
        dim; params and target_params never do (grads come from whole
        params — no gather in the backward)."""
        import jax.tree_util as jtu

        cfg = tiny_test()
        _, state = init_train_state(cfg, jax.random.PRNGKey(0))
        mesh = make_mesh(dp=2, tp=2, fsdp=2)
        sh = train_state_shardings(state, mesh)
        carriers = [
            process_name(p)
            for p, s in jtu.tree_flatten_with_path(sh)[0]
            if "fsdp" in s.spec
        ]
        assert carriers, "no moment leaf picked up the fsdp axis"
        assert all(".mu." in n or ".nu." in n for n in carriers)
        # the big recurrent kernel's moments are among them
        assert "opt_state.*.*.mu.params.core.wh" in carriers

    def test_fsdp_train_step_matches_single_device(self):
        """One update on the dp=4 x fsdp=2 mesh with moments fsdp-sharded
        reproduces the unsharded update, and the output moments KEEP
        their fsdp sharding (the optimizer ran sharded instead of
        gathering). tp stays 1: config.validate blocks the tp x fsdp
        composition (3-axis tp sharding miscompiles the recurrent scan
        under the current SPMD partitioner — this test's equivalence
        check is exactly what caught it)."""
        cfg = tiny_test().replace(lstm_backend="scan")
        net, state0 = init_train_state(cfg, jax.random.PRNGKey(0))
        batch = random_batch(cfg)
        step = make_train_step(cfg, net, donate=False)

        ref_state, ref_m, _ = step(state0, batch)

        mesh = make_mesh(dp=4, tp=1, fsdp=2)
        sh = train_state_shardings(state0, mesh)
        fs_state = jax.device_put(state0, sh)
        mu_wh = fs_state.opt_state[1][0].mu["params"]["core"]["wh"]
        assert "fsdp" in mu_wh.sharding.spec
        fs_batch = type(batch)(*shard_batch(mesh, tuple(batch)))
        fs_state, fs_m, _ = step(fs_state, fs_batch)

        np.testing.assert_allclose(
            float(fs_m["loss"]), float(ref_m["loss"]), rtol=1e-5
        )
        for a, b in zip(
            jax.tree.leaves(fs_state.params), jax.tree.leaves(ref_state.params)
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        out_mu = fs_state.opt_state[1][0].mu["params"]["core"]["wh"]
        assert "fsdp" in out_mu.sharding.spec
        # really partitioned: each fsdp shard holds half the bytes
        assert {s.data.size for s in out_mu.addressable_shards} == {out_mu.size // 2}

    def test_snapshot_topology_is_fsdp_agnostic(self):
        """Topology manifests record (plane, dp, tp, process layout) ONLY
        — fsdp shards optimizer state, never the replay layout, so
        resuming a snapshot under a different --fsdp must not (and
        structurally cannot) trip TopologyMismatch."""
        from r2d2_tpu.replay.replay_buffer import ReplayBuffer
        from r2d2_tpu.replay.snapshot import snapshot_topology

        cfg = tiny_test()
        topo = snapshot_topology(ReplayBuffer(cfg), tp=1)
        assert "fsdp" not in {k.lower() for k in topo}


class TestManualPartitionStep:
    """learner.make_manual_train_step — the explicitly shard_mapped
    tp×fsdp×dp train step (ISSUE 16 tentpole). Every case checks against
    the unsharded single-device reference: the manual collectives (gate
    all-gather seam, head psum, grad psums, ZeRO-2 reduce-scatter,
    grouped global-norm) must reproduce its numerics, not merely run."""

    def _manual_setup(self, cfg, dp, tp, fsdp):
        from r2d2_tpu.learner import make_manual_train_step
        from r2d2_tpu.parallel import manual_batch_sharding

        net, state0 = init_train_state(cfg, jax.random.PRNGKey(0))
        batch = random_batch(cfg)
        mesh = make_mesh(dp=dp, tp=tp, fsdp=fsdp)
        m_state = jax.device_put(state0, train_state_shardings(state0, mesh))
        sh = manual_batch_sharding(mesh)
        m_batch = jax.tree.map(lambda x: jax.device_put(x, sh), batch)
        step = make_manual_train_step(cfg, mesh, donate=False)
        return net, state0, batch, m_state, m_batch, step

    @pytest.mark.parametrize("precision", ["fp32", "bf16"])
    def test_tp_fsdp_matches_unsharded(self, precision):
        """The cell PR 14's validate() had to block: tp=2 x fsdp=2 x dp=2
        on the 8-device mesh, now through the manual path. Two updates so
        the second consumes evolved (sharded) Adam moments."""
        # bf16 tolerances absorb rounding-order differences: the manual
        # path's gate all-gather seam and grouped reductions accumulate
        # bf16 products in a different order than the fused reference
        atol = 1e-5 if precision == "fp32" else 5e-4
        rtol = 1e-4 if precision == "fp32" else 2e-3
        cfg = tiny_test().replace(
            lstm_backend="scan", tp_size=2, fsdp_size=2, dp_size=2,
            precision=precision,
        )
        assert cfg.resolved_partitioning == "manual"
        net, state0, batch, m_state, m_batch, step = self._manual_setup(
            cfg, dp=2, tp=2, fsdp=2
        )
        ref = make_train_step(cfg, net, donate=False)
        ref_state, ref_m, ref_prio = ref(state0, batch)
        ref_state, ref_m2, _ = ref(ref_state, batch)
        m_state2, m_m, m_prio = step(m_state, m_batch)
        m_state2, m_m2, _ = step(m_state2, m_batch)

        np.testing.assert_allclose(
            float(m_m["loss"]), float(ref_m["loss"]), rtol=rtol
        )
        np.testing.assert_allclose(
            float(m_m["grad_norm"]), float(ref_m["grad_norm"]), rtol=rtol
        )
        np.testing.assert_allclose(
            np.asarray(m_prio), np.asarray(ref_prio), atol=atol, rtol=rtol
        )
        np.testing.assert_allclose(
            float(m_m2["loss"]), float(ref_m2["loss"]), rtol=rtol
        )
        for a, b in zip(
            jax.tree.leaves(m_state2.params), jax.tree.leaves(ref_state.params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=atol
            )
        # params keep the table's Megatron layout on the way out
        wi = m_state2.params["params"]["core"]["wi"]
        assert wi.sharding.spec == P(None, "tp")

    def test_zero2_moment_shards_and_update_equality(self):
        """fsdp=4 with the batch split over (dp, fsdp): gradients land on
        the Adam moment shards via a TRUE reduce-scatter, Adam runs on
        quarters, and the gathered updates still reproduce the replicated
        single-device Adam exactly."""
        cfg = tiny_test().replace(
            lstm_backend="scan", tp_size=1, fsdp_size=4, dp_size=2,
            partitioning="manual",
        )
        net, state0, batch, m_state, m_batch, step = self._manual_setup(
            cfg, dp=2, tp=1, fsdp=4
        )
        ref_state, ref_m, _ = make_train_step(cfg, net, donate=False)(
            state0, batch
        )
        m_state2, m_m, _ = step(m_state, m_batch)
        np.testing.assert_allclose(
            float(m_m["loss"]), float(ref_m["loss"]), rtol=1e-5
        )
        for a, b in zip(
            jax.tree.leaves(m_state2.params), jax.tree.leaves(ref_state.params)
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        for mom in ("mu", "nu"):
            out = getattr(m_state2.opt_state[1][0], mom)["params"]["core"]["wh"]
            refm = getattr(ref_state.opt_state[1][0], mom)["params"]["core"]["wh"]
            assert "fsdp" in out.sharding.spec
            # really partitioned: each fsdp member holds a quarter
            assert {s.data.size for s in out.addressable_shards} == {out.size // 4}
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(refm), atol=1e-7
            )

    def test_resume_roundtrip_across_changed_tp_fsdp_layout(self, tmp_path):
        """A checkpoint written from a tp=2 x fsdp=2 manual run restores
        into a tp=1 x fsdp=2 manual layout (checkpoints are GLOBAL trees;
        the template's shardings place the restored leaves) and training
        continues with the numerics of an unsharded run that never
        stopped."""
        from r2d2_tpu.utils.checkpoint import restore_checkpoint, save_checkpoint

        cfg_a = tiny_test().replace(
            lstm_backend="scan", tp_size=2, fsdp_size=2, dp_size=2,
            checkpoint_dir=str(tmp_path),
        )
        net, state0, batch, m_state, m_batch, step_a = self._manual_setup(
            cfg_a, dp=2, tp=2, fsdp=2
        )
        ref = make_train_step(cfg_a, net, donate=False)
        ref_state, _, _ = ref(state0, batch)
        ref_state, _, _ = ref(ref_state, batch)

        m_state1, _, _ = step_a(m_state, m_batch)
        save_checkpoint(str(tmp_path), jax.device_get(m_state1), 0, 0.0)

        cfg_b = cfg_a.replace(
            tp_size=1, fsdp_size=2, dp_size=4, partitioning="manual"
        )
        from r2d2_tpu.learner import make_manual_train_step
        from r2d2_tpu.parallel import manual_batch_sharding

        mesh_b = make_mesh(dp=4, tp=1, fsdp=2)
        _, template = init_train_state(cfg_b, jax.random.PRNGKey(1))
        template = jax.device_put(
            template, train_state_shardings(template, mesh_b)
        )
        restored, _, _ = restore_checkpoint(str(tmp_path), template)
        sh_b = manual_batch_sharding(mesh_b)
        batch_b = jax.tree.map(lambda x: jax.device_put(x, sh_b), batch)
        final, _, _ = make_manual_train_step(cfg_b, mesh_b, donate=False)(
            restored, batch_b
        )
        assert int(final.step) == 2
        for a, b in zip(
            jax.tree.leaves(final.params), jax.tree.leaves(ref_state.params)
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


class TestConfigKnobs:
    def test_fsdp_size_validation(self):
        with pytest.raises(ValueError, match="fsdp_size"):
            tiny_test().replace(fsdp_size=0)
        with pytest.raises(ValueError, match="multihost"):
            tiny_test().replace(
                fsdp_size=2, replay_plane="multihost", tp_size=1
            )
        # tp x fsdp stays blocked on the LEGACY GSPMD path (scan
        # miscompiles on a 3-axis mesh under the SPMD partitioner) — but
        # only there: the default 'auto' now resolves to the manual-
        # partition step, which validates clean
        with pytest.raises(ValueError, match="composes fsdp with dp only"):
            tiny_test().replace(
                fsdp_size=2, tp_size=2, lstm_backend="scan",
                partitioning="gspmd",
            )
        cfg = tiny_test().replace(fsdp_size=2, tp_size=2, lstm_backend="scan")
        cfg.validate()
        assert cfg.resolved_partitioning == "manual"

    @pytest.mark.parametrize("name, value", [
        ("seq_fused_dwh", True), ("seq_grad_checkpoint", 5),
        ("backward_arm", "default"), ("backward_residual_budget_mb", 128),
    ])
    def test_removed_backward_arm_knobs_are_unknown_fields(self, name, value):
        """PR 37: the LSTM sequence kernel has one backward and no option
        selects it; a configuration that still names one of the four
        removed fields fails like any unknown field (MIGRATION.md)."""
        with pytest.raises(TypeError, match=name):  # a configuration file
            tiny_test().replace(**{name: value})
        with pytest.raises(ValueError, match=f"unknown config field '{name}'"):
            parse_overrides([f"{name}={value}"])  # a command line's --set
