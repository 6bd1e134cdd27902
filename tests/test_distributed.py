"""Distribution-layer tests that run on a single CPU device: sharding rules
produce divisibility-valid specs for every arch on the production meshes
(validated against an AbstractMesh — no devices needed), ZeRO-1 adds data
sharding, cache rules hit heads/sequence fallbacks, pipeline math."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.distributed import sharding as shd
from repro.distributed.pipeline import bubble_fraction
from repro.launch import cells as C


def abstract_mesh(multi_pod=False):
    if multi_pod:
        sizes, names = (2, 16, 16), ("pod", "data", "model")
    else:
        sizes, names = (16, 16), ("data", "model")
    return AbstractMesh(sizes, names)


def _axis_size(mesh, ax):
    if ax is None:
        return 1
    names = ax if isinstance(ax, tuple) else (ax,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


def assert_specs_divide(tree_shapes, tree_specs, mesh, where=""):
    flat_shapes = jax.tree.leaves(tree_shapes)
    flat_specs = jax.tree.leaves(tree_specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_shapes) == len(flat_specs)
    for leaf, spec in zip(flat_shapes, flat_specs):
        spec_t = tuple(spec) + (None,) * (len(leaf.shape) - len(tuple(spec)))
        for dim, ax in zip(leaf.shape, spec_t):
            size = _axis_size(mesh, ax)
            assert dim % size == 0, (
                f"{where}: dim {dim} not divisible by {ax} ({size}) "
                f"for leaf {leaf.shape} spec {spec}"
            )


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("multi", [False, True])
def test_param_specs_divide_all_archs(arch, multi):
    cfg = get_config(arch)
    mesh = abstract_mesh(multi)
    shapes = C.params_shapes(cfg)
    specs = shd.param_specs(shapes, cfg, mesh)
    assert_specs_divide(shapes, specs, mesh, where=f"{arch} params")


@pytest.mark.parametrize("arch", ["gemma_7b", "granite_moe_3b_a800m", "mamba2_2_7b"])
def test_zero1_adds_data_sharding(arch):
    cfg = get_config(arch)
    mesh = abstract_mesh()
    shapes = C.train_state_shapes(cfg)
    pspecs = shd.param_specs(shapes["params"], cfg, mesh)
    oz = shd.zero1_specs(shapes["opt"], pspecs, mesh)
    assert_specs_divide(shapes["opt"]["master"], oz["master"], mesh,
                        where=f"{arch} zero1 master")
    # at least the big 2D masters must pick up a data axis
    flat = [
        (l, s) for l, s in zip(
            jax.tree.leaves(shapes["opt"]["m"]),
            jax.tree.leaves(oz["m"], is_leaf=lambda x: isinstance(x, P)),
        )
        if np.prod(l.shape) > 1e6
    ]
    assert any("data" in str(s) for _, s in flat), "no ZeRO sharding applied"


@pytest.mark.parametrize("arch", ARCHS)
def test_cache_specs_divide(arch):
    cfg = get_config(arch)
    mesh = abstract_mesh()
    cell = C.SHAPES["decode_32k"]
    shapes = C.cache_shapes(cfg, cell.batch, cell.seq)
    specs = C.cache_specs(cfg, shapes, mesh, cell.batch)
    assert_specs_divide(shapes, specs, mesh, where=f"{arch} cache")


def test_kv_cache_head_vs_sequence_fallback():
    """gemma (16 kv heads) shards heads; internvl (8 kv heads) must fall
    back to split-KV over the sequence axis."""
    mesh = abstract_mesh()
    g = get_config("gemma_7b")
    shapes = C.cache_shapes(g, 128, 32768)
    specs = C.cache_specs(g, shapes, mesh, 128)
    flat = [tuple(s) for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))]
    # (L, B, H=16, S, hd): heads shard -> model at index -3
    assert all(s[-3] == "model" for s in flat if len(s) == 5), flat

    iv = get_config("internvl2_26b")
    shapes = C.cache_shapes(iv, 128, 32768)
    specs = C.cache_specs(iv, shapes, mesh, 128)
    flat = [tuple(s) for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))]
    # (L, B, H=8, S, hd): 8 heads don't divide 16 -> split-KV on S (index -2)
    assert all(s[-2] == "model" and s[-3] is None for s in flat if len(s) == 5), flat


def test_residual_spec_sequence_parallel():
    mesh = abstract_mesh()
    spec = shd.residual_spec(mesh, batch=256, seq=4096)
    assert tuple(spec) == ("data", "model", None)
    # odd seq: SP dropped
    spec = shd.residual_spec(mesh, batch=256, seq=1000)
    assert tuple(spec) == ("data", None, None)


def test_batch_spec_multi_pod():
    mesh = abstract_mesh(multi_pod=True)
    assert tuple(shd.batch_spec(mesh, 256)) == (("pod", "data"),)
    assert tuple(shd.batch_spec(mesh, 1)) == (None,)


def test_moe_ep_vs_tp_rule():
    mesh = abstract_mesh()
    ds = get_config("deepseek_v2_lite_16b")  # 64 experts % 16 == 0 -> EP
    shapes = C.params_shapes(ds)
    specs = shd.param_specs(shapes, ds, mesh)
    moe_spec = specs["layers"]["moe"]["w_gate"]
    assert "model" == tuple(moe_spec)[1]  # (L, E, D, F): EP on expert axis

    gr = get_config("granite_moe_3b_a800m")  # 40 experts -> TP inside expert
    shapes = C.params_shapes(gr)
    specs = shd.param_specs(shapes, gr, mesh)
    moe_spec = specs["layers"]["moe"]["w_gate"]
    t = tuple(moe_spec)
    assert t[1] is None and t[-1] == "model"


def test_pipeline_bubble_fraction():
    assert bubble_fraction(1, 4) == pytest.approx(3 / 4)
    assert bubble_fraction(16, 4) == pytest.approx(3 / 19)
    assert bubble_fraction(64, 2) < 0.02


def test_supported_matrix():
    """The 40-cell grid: long_500k runs only for sub-quadratic archs."""
    runs = {}
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape, cell in C.SHAPES.items():
            ok, _ = C.supported(cfg, cell)
            runs[(arch, shape)] = ok
    assert runs[("mamba2_2_7b", "long_500k")]
    assert runs[("hymba_1_5b", "long_500k")]
    assert not runs[("gemma_7b", "long_500k")]
    assert sum(runs.values()) == 10 * 4 - 8  # 8 full-attention skips
