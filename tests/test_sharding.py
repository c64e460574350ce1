"""Sharding rules: spec table correctness + 16-way divisibility for EVERY
assigned arch's parameters (via eval_shape — no allocation)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import repro.configs as configs
from repro.dist import params as dist_params
from repro.dist.sharding import physical_spec, use_mesh
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as tf

MODEL_WAYS = 16


def _spec_tree(cfg):
    sds = jax.eval_shape(lambda k: tf.init(k, cfg), jax.random.PRNGKey(0))
    return sds, dist_params.spec_tree(sds)


@pytest.mark.parametrize("arch", list(configs.ARCHS))
def test_model_axis_dims_divide_16(arch):
    """Every dim mapped to the 16-way "model" axis must divide evenly —
    this is the check that caught llama4's 40-head / seamless-vocab issues."""
    cfg = configs.get(arch)
    sds, specs = _spec_tree(cfg)
    flat_s, _ = jax.tree_util.tree_flatten_with_path(sds)
    flat_p = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(flat_s, flat_p):
        for dim, ax in enumerate(spec):
            if ax == "model":
                assert leaf.shape[dim] % MODEL_WAYS == 0, (
                    f"{arch}: {jax.tree_util.keystr(path)} dim {dim} "
                    f"= {leaf.shape[dim]} not divisible by {MODEL_WAYS}")


def test_moe_experts_on_model_axis():
    cfg = configs.get("llama4-scout-17b-a16e")
    _, specs = _spec_tree(cfg)
    moe_spec = specs["segments"][0]["ffn"]["w1"]
    assert moe_spec == P(None, "model", None, None)   # [L, E, d, f]: EP on E
    shared = specs["segments"][0]["ffn"]["shared"]["w1"]
    assert shared == P(None, None, "model")           # stacked dense


def test_attention_specs():
    cfg = configs.get("qwen2-1.5b")
    _, specs = _spec_tree(cfg)
    blk = specs["segments"][0]
    assert blk["attn"]["wq"] == P(None, None, "model")
    assert blk["attn"]["wo"] == P(None, "model", None)
    assert blk["attn"]["bq"] == P(None, "model")
    assert blk["norm1"]["w"] == P(None, None)


def test_mamba_specs():
    cfg = configs.get("falcon-mamba-7b")
    _, specs = _spec_tree(cfg)
    blk = specs["segments"][0]["mixer"]
    assert blk["in_proj"] == P(None, None, "model")
    assert blk["out_proj"] == P(None, "model", None)
    assert blk["A_log"] == P(None, "model", None)


def test_physical_spec_filters_missing_axes():
    mesh = make_host_mesh(1, 1)   # only (data, model) with size 1
    spec = physical_spec(("batch", None, "model"), mesh)
    assert spec == P("data", None, "model")


def test_constrain_is_noop_without_mesh():
    from repro.dist.sharding import constrain
    x = jnp.ones((4, 4))
    assert constrain(x, "batch", "model") is x


def test_embed_sharded_lookup_matches_plain(monkeypatch):
    """shard_map embedding == plain take on a 1x1 mesh."""
    cfg = configs.get_smoke("llama3.2-1b")
    params = tf.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    from repro.models import layers
    plain = jnp.take(params["embed"]["table"], toks, axis=0)
    mesh = make_host_mesh(1, 1)
    with use_mesh(mesh):
        sharded = jax.jit(lambda p, t: layers.embed(p, t, cfg))(
            params["embed"], toks)
    assert jnp.allclose(plain, sharded)


def test_serving_mesh_replicates_absent_axes():
    """A serving mesh has only the data axis: "batch" shards onto it,
    while logical axes with no physical home on this mesh (seeds, model)
    silently replicate — the absent-axis fallback the mesh-sharded
    engines lean on."""
    from repro.launch.mesh import make_serving_mesh
    mesh = make_serving_mesh(4)        # the four virtual test devices
    assert tuple(mesh.axis_names) == ("data",)
    assert physical_spec(("batch", None), mesh) == P("data", None)
    assert physical_spec(("seeds", "batch"), mesh) == P(None, "data")
    assert physical_spec(("model",), mesh) == P(None)
    with pytest.raises(ValueError):
        make_serving_mesh(0)


def test_constrain_identity_on_one_shard_serving_mesh():
    """Sharding constraints on a 1-device serving mesh change placement
    metadata only — values round-trip bitwise."""
    from repro.dist.sharding import constrain
    from repro.launch.mesh import make_serving_mesh
    mesh = make_serving_mesh(1)
    x = jnp.arange(32.0).reshape(4, 8)
    with use_mesh(mesh):
        y = jax.jit(lambda v: constrain(v, "batch", None))(x)
        z = jax.jit(lambda v: constrain(v, "seeds", "batch"))(x)
    assert jnp.array_equal(y, x) and jnp.array_equal(z, x)


def test_serving_mesh_refuses_more_shards_than_devices():
    """A mesh engine never quietly replicates: asking for more shards than
    the host has devices raises instead of capping the mesh."""
    from repro.launch.mesh import make_serving_mesh
    n = len(jax.devices())
    assert make_serving_mesh(n).devices.size == n
    with pytest.raises(ValueError, match="needs"):
        make_serving_mesh(n + 1)
