"""The one place `shard_map` is imported from jax.

Every shard_map call in the codebase routes through this wrapper (the
`raw-shard-map-import` lint, analysis/ast_rules.py, keeps it so) so the
manual-axis convention is stated once: `axis_names` is the set of mesh
axes the body is manual over, any other mesh axis stays GSPMD-auto, and
`axis_names=None` means FULLY manual (the tp x fsdp train step depends on
that). `jax.shard_map` is the top-level export of the installed jax
(pyproject.toml pins the floor); no older-API branch is kept."""

from __future__ import annotations

from jax import shard_map as _shard_map


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=True, axis_names=None):
    kwargs = dict(
        mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
    )
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return _shard_map(f, **kwargs)
