"""Sharding-rule validity for every (arch × mesh) without real devices.

Uses AbstractMesh so the 512-way production meshes can be validated in the
same process as the 1-device tests (jax locks the device count at init).
"""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.configs as C
from repro.configs.shapes import cache_specs
from repro.distributed import sharding as SH
from repro.distributed.axes import abstract_mesh
from repro.kernels.paged_attention import KV_HEAD_AXIS
from repro.models import model as M

MESHES = {
    "single": abstract_mesh((16, 16), ("data", "model")),
    "multi": abstract_mesh((2, 16, 16), ("pod", "data", "model")),
}


def _axis_sizes(mesh):
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def _check_spec_tree(mesh, shapes_tree, specs_tree, *, allow_uneven=False):
    sizes = _axis_sizes(mesh)
    leaves_shape = jax.tree.leaves(shapes_tree)
    leaves_spec = jax.tree.leaves(
        specs_tree, is_leaf=lambda x: isinstance(x, P)
    )
    assert len(leaves_shape) == len(leaves_spec)
    for leaf, spec in zip(leaves_shape, leaves_spec):
        assert isinstance(spec, P)
        assert len(spec) <= len(leaf.shape), (leaf.shape, spec)
        used = []
        for dim, entry in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            n = int(np.prod([sizes[a] for a in axes]))
            used.extend(axes)
            if not allow_uneven:
                assert dim % n == 0, (leaf.shape, spec)
        assert len(used) == len(set(used)), f"axis reused: {spec}"


@pytest.mark.parametrize("arch", C.arch_ids())
@pytest.mark.parametrize("mesh_name", ["single", "multi"])
def test_param_specs_valid(arch, mesh_name):
    cfg = C.get_config(arch)
    mesh = MESHES[mesh_name]
    params_shape = jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))
    )
    for mode in ("train", "serve"):
        specs = SH.param_pspecs(cfg, mesh, params_shape, mode=mode)
        _check_spec_tree(mesh, params_shape, specs)


@pytest.mark.parametrize("arch", ["qwen1.5-110b", "mamba2-130m",
                                  "h2o-danube-3-4b", "deepseek-v3-671b"])
def test_cache_specs_valid(arch):
    cfg = C.get_config(arch)
    mesh = MESHES["single"]
    cs = cache_specs(cfg, 128, 32768)
    specs = SH.cache_pspecs(cfg, mesh, cs)
    _check_spec_tree(mesh, cs, specs)


def test_small_model_is_replicated_in_train():
    cfg = C.get_config("mamba2-130m")
    mesh = MESHES["single"]
    params_shape = jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))
    )
    specs = SH.param_pspecs(cfg, mesh, params_shape, mode="train")
    for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        assert all(e is None for e in s), s


def test_serve_mode_uses_all_axes_for_110b():
    cfg = C.get_config("qwen1.5-110b")
    mesh = MESHES["single"]
    params_shape = jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))
    )
    specs = SH.param_pspecs(cfg, mesh, params_shape, mode="serve")
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P)
    )[0]
    # FFN weights must be sharded over (data, model) = 256-way resident
    found = False
    for path, spec in flat:
        if "w_gate" in SH._path_str(path):
            assert any(isinstance(e, tuple) and set(e) == {"data", "model"}
                       for e in spec if e is not None), spec
            found = True
    assert found


@pytest.mark.parametrize("arch", C.arch_ids())
@pytest.mark.parametrize("tp", [2, 4, 8])
def test_paged_cache_pool_specs_valid(arch, tp):
    """Every cache family's pool specs stay valid on 2/4/8-way model axes:
    sharded axes divide the pool shapes (the adapter emits head sharding
    only when the kv-head axis divides), page tables never enter the tree,
    and the divisibility invariant holds for the L-stacked pool layout."""
    import jax.numpy as jnp

    from repro.models import adapters as A

    cfg = C.get_config(arch, smoke=True, dtype=jnp.float32)
    if A.unsupported_message(cfg) is not None:
        pytest.skip("family is Server-only (no paged pools)")
    mesh = abstract_mesh((1, tp), ("data", "model"))
    pools = jax.eval_shape(lambda: M.init_paged_cache(cfg, 2, 5, 8, 32))
    specs = SH.paged_cache_pspecs(cfg, mesh, pools)
    _check_spec_tree(mesh, pools, specs)
    # when the kv-head axis divides, paged K/V pools must actually shard,
    # on the kv-head axis, which leads each page in either stored order
    if cfg.n_kv_heads and cfg.n_kv_heads % tp == 0 and any(
        isinstance(ad, A.PagedAttnAdapter) for ad in A.all_adapters(cfg)
    ):
        flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert any("model" in tuple(s) for s in flat), specs
        axis = KV_HEAD_AXIS
        for si in range(len(M.layer_segments(cfg))):
            seg_pools, seg_specs = pools[f"seg{si}"], specs[f"seg{si}"]
            if "k_pages" not in seg_pools.get("attn", {}):
                continue
            for name in ("k_pages", "v_pages"):
                spec = tuple(seg_specs["attn"][name])
                assert spec.index("model") == axis, (name, spec)
                assert seg_pools["attn"][name].shape[axis] == cfg.n_kv_heads


@pytest.mark.parametrize("tp", [4, 8])
def test_paged_sharding_validation_rejects_nondividing(tp):
    """Construction-time rejection: paged kv-heads that cannot divide the
    model axis raise with the valid TP sizes named (no silent replication)."""
    import jax.numpy as jnp

    cfg = C.get_config("minicpm-2b", smoke=True, dtype=jnp.float32)  # hkv=6
    mesh = abstract_mesh((1, tp), ("data", "model"))
    with pytest.raises(ValueError, match="n_kv_heads=6"):
        SH.validate_paged_sharding(cfg, mesh)
    # 2-way divides; MLA (no paged head axis) passes at any size
    SH.validate_paged_sharding(cfg, abstract_mesh((1, 2), ("data", "model")))
    mla = C.get_config("deepseek-v3-671b", smoke=True, dtype=jnp.float32)
    SH.validate_paged_sharding(mla, mesh)


def test_zero_extension_shards_moments_512_ways():
    cfg = C.get_config("deepseek-v3-671b")
    mesh = MESHES["multi"]
    params_shape = jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))
    )
    specs = SH.param_pspecs(cfg, mesh, params_shape, mode="train")
    sizes = _axis_sizes(mesh)
    # the expert weights (dominant storage) must be sharded >= 256 ways
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P)
    )[0]
    for path, spec in flat:
        if "moe/w_gate" in SH._path_str(path).replace("seg1/", "moe_") or \
           ("w_gate" in SH._path_str(path) and "moe" in SH._path_str(path)):
            ways = 1
            for e in spec:
                if e is None:
                    continue
                for a in (e if isinstance(e, tuple) else (e,)):
                    ways *= sizes[a]
            assert ways >= 256, (spec, ways)
