"""Compile the main-path Pallas kernels for a described TPU v5e (no chip).

Every kernel the CNN explanation path launches is lowered and compiled by
the TPU compiler for one chip of a described ``v5e:2x2`` topology, at the
paper CNN's widths (Table III channels, serving batch 8, top-3 seed
panels), and must come out as a Mosaic kernel (``tpu_custom_call``).
Nothing runs: this catches what interpret mode cannot — unsupported
lowerings, tiling misalignment, scoped-VMEM overruns — at no chip time.

The topology is described inside a fixture (never at import: only one
process may load the TPU library), and the tests skip where it cannot be
described.  Kernel modules pick interpret mode from the CPU backend here,
so each test patches their ``interpret_mode`` binding to compile instead.
The persistent compilation cache is off around these compiles: an entry
compiled for a described chip cannot be read back without one.
"""
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.models import cnn

KERNEL_MODULES = ("conv2d.conv2d", "conv2d.fxp", "pool.pool",
                  "relu_mask.relu_mask", "vmm.vmm", "vmm.fxp")
METHODS = ("saliency", "deconvnet", "guided")
N, S = 8, 3                       # serving batch, top-3 seed panel
DTYPE = {"f32": jnp.float32, "bf16": jnp.bfloat16, "fxp16": jnp.int16}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compiled(monkeypatch):
    """Kernels compile (interpret=False) even though the backend is CPU."""
    for name in KERNEL_MODULES:
        mod = importlib.import_module(f"repro.kernels.{name}")
        monkeypatch.setattr(mod, "interpret_mode",
                            lambda interpret=None: False)


def compile_for(chip, fn, *shapes):
    """Compile ``fn`` for the described chip; return its HLO text."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _mod(name):
    return importlib.import_module(f"repro.kernels.{name}")


@pytest.mark.parametrize("precision", ["f32", "bf16", "fxp16"])
def test_conv_fwd_compiles(one_chip, compiled, precision):
    dt = DTYPE[precision]
    fn = (_mod("conv2d.fxp").conv2d_fxp_pallas if precision == "fxp16"
          else _mod("conv2d.conv2d").conv2d_pallas)
    compile_for(one_chip, fn, ((N, 32, 32, 32), dt), ((3, 3, 32, 64), dt))


@pytest.mark.parametrize("precision", ["f32", "bf16", "fxp16"])
@pytest.mark.parametrize("method", METHODS)
def test_conv_bwd_fused_with_pool_and_mask_compiles(one_chip, compiled,
                                                    method, precision):
    """conv1's backward: unpool (16x16 -> 32x32) + mask gate + BP dot."""
    dt = DTYPE[precision]
    fn = (_mod("conv2d.fxp").conv2d_bwd_fused_fxp_pallas
          if precision == "fxp16"
          else _mod("conv2d.conv2d").conv2d_bwd_fused_pallas)

    def bwd(g, wt, idx, mask):
        return fn(g, wt, pool_idx=idx, gate=True, method=method,
                  relu_mask=None if method == "deconvnet" else mask)

    compile_for(one_chip, bwd, ((S, N, 16, 16, 32), dt), ((3, 3, 32, 32), dt),
                ((N, 16, 16, 8), jnp.uint8), ((N, 32, 32, 4), jnp.uint8))


@pytest.mark.parametrize("precision", ["f32", "bf16", "fxp16"])
def test_conv_kernels_compile_within_planned_footprint(one_chip, compiled,
                                                       monkeypatch,
                                                       precision):
    """The planner's tiled-VMEM footprint is enough for the compiler: every
    conv launch of the paper CNN (one example per grid cell, a top-3
    panel) compiles with its scoped-VMEM limit set to exactly the bytes
    ``cnn_plan_footprints`` models for it on the v5e profile."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.plan import cnn_kernel_shapes, cnn_plan_footprints, plan_cnn
    cfg = cnn.CNNConfig()
    plan = plan_cnn(cfg, device="tpu-v5e", precision=precision, seeds=S)
    fps = cnn_plan_footprints(cfg, plan, precision=precision, seeds=S)
    fxp = precision == "fxp16"
    conv = _mod("conv2d.fxp" if fxp else "conv2d.conv2d")
    fwd = conv.conv2d_fxp_pallas if fxp else conv.conv2d_pallas
    bwd = (conv.conv2d_bwd_fused_fxp_pallas if fxp
           else conv.conv2d_bwd_fused_pallas)
    dt = DTYPE[precision]
    for key, family, kw in cnn_kernel_shapes(cfg, batch=1, seeds=S):
        limit = fps[key].vmem_bytes
        monkeypatch.setattr(conv, "compiler_params", lambda limit=limit:
                            pltpu.CompilerParams(vmem_limit_bytes=limit))
        co_tile = getattr(plan.get(key), "co_tile", None)
        if family == "conv2d_fwd":
            compile_for(one_chip,
                        lambda x, w: fwd(x, w, co_tile=co_tile),
                        ((1, kw["h"], kw["w"], kw["cin"]), dt),
                        ((kw["k"], kw["k"], kw["cin"], kw["cout"]), dt))
        elif family == "conv2d_bwd":
            hg, wg, c, pooled = kw["hg"], kw["wg"], kw["c"], kw["pooled"]
            h, w = (2 * hg, 2 * wg) if pooled else (hg, wg)

            def step(g, wt, idx, mask, pooled=pooled):
                return bwd(g, wt, pool_idx=idx if pooled else None,
                           relu_mask=mask, method="guided", co_tile=co_tile)

            compile_for(one_chip, step, ((S, 1, hg, wg, c), dt),
                        ((kw["k"], kw["k"], c, kw["cout"]), dt),
                        ((1, hg, wg, -(-c // 4)), jnp.uint8),
                        ((1, h, w, -(-c // 8)), jnp.uint8))


@pytest.mark.parametrize("precision", ["f32", "bf16", "fxp16"])
def test_maxpool_compiles(one_chip, compiled, precision):
    compile_for(one_chip, _mod("pool.pool").maxpool_fwd_pallas,
                ((N, 32, 32, 32), DTYPE[precision]))


@pytest.mark.parametrize("precision", ["f32", "bf16", "fxp16"])
@pytest.mark.parametrize("rows,cols", [(N * 32 * 32, 32), (N, 128)],
                         ids=["conv", "fc"])
def test_relu_fwd_compiles(one_chip, compiled, precision, rows, cols):
    compile_for(one_chip, _mod("relu_mask.relu_mask").relu_fwd_pallas,
                ((rows, cols), DTYPE[precision]))


@pytest.mark.parametrize("precision", ["f32", "bf16", "fxp16"])
def test_vmm_fwd_compiles(one_chip, compiled, precision):
    dt = DTYPE[precision]
    fn = (_mod("vmm.fxp").vmm_fxp_pallas if precision == "fxp16"
          else _mod("vmm.vmm").vmm_pallas)
    compile_for(one_chip, fn, ((N, 4096), dt), ((4096, 128), dt))


@pytest.mark.parametrize("precision", ["f32", "bf16", "fxp16"])
def test_vmm_bwd_fused_compiles(one_chip, compiled, precision):
    """FC1's backward: mask gate + transposed dot, 3 seeds."""
    dt = DTYPE[precision]
    fn = (_mod("vmm.fxp").vmm_bwd_fused_fxp_pallas if precision == "fxp16"
          else _mod("vmm.vmm").vmm_bwd_fused_pallas)

    def bwd(g, wt, mask):
        return fn(g, wt, relu_mask=mask, method="guided")

    compile_for(one_chip, bwd, ((S, N, 128), dt), ((128, 4096), dt),
                ((N, 16), jnp.uint8))


@pytest.mark.parametrize("matmul_precision", [None, "highest"],
                         ids=["default", "highest"])
@pytest.mark.parametrize("precision", ["f32", "bf16", "fxp16"])
def test_backward_seeds_program_compiles(one_chip, compiled, precision,
                                         matmul_precision):
    """The whole jitted explain: residual forward + seed-batched fused BP
    of the full-width Table III CNN — every kernel launch of the path.
    Also under ``jax.default_matmul_precision("highest")`` (what a float
    reference runs under): the kernels' dots pin their own precision, as
    Mosaic refuses an fp32 contract on bf16/int8 operands."""
    cfg = cnn.CNNConfig()
    params = jax.eval_shape(lambda: cnn.init(jax.random.PRNGKey(0), cfg))

    def explain(params, x):
        logits, res = cnn.forward_with_residuals(params, x, cfg, "guided",
                                                 precision)
        _, idx = jax.lax.top_k(logits, S)
        seeds = jax.nn.one_hot(idx.T, cfg.num_classes, dtype=logits.dtype)
        return cnn.backward_seeds(params, res, seeds, cfg, "guided",
                                  precision)

    p_spec = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    x_spec = jax.ShapeDtypeStruct((N, 32, 32, 3), jnp.float32,
                                  sharding=one_chip)
    with jax.default_matmul_precision(matmul_precision):
        text = jax.jit(explain).lower(p_spec, x_spec).compile().as_text()
    # 4 conv + 2 pool + 5 relu/mask + 2 FC forwards, 4 conv + 2 FC backwards
    assert text.count("tpu_custom_call") >= 12


#: the kernel families a ``pallas_call`` may be named after
KERNEL_FAMILIES = ("conv2d_fwd", "conv2d_bwd", "vmm_fwd", "vmm_bwd",
                   "maxpool_fwd", "maxpool_bwd", "relu_mask_fwd",
                   "relu_mask_bwd", "ssm_scan")


@pytest.mark.parametrize("precision", ["f32", "fxp16"])
def test_served_programs_name_every_kernel_by_family(one_chip, compiled,
                                                     precision):
    """The serving engine's two programs, the residual forward and the
    seed-batched replay, compiled for the chip: every Mosaic kernel is an
    instruction named after its family, so the device trace names it too."""
    import re

    cfg = cnn.CNNConfig()
    params = jax.eval_shape(lambda: cnn.init(jax.random.PRNGKey(0), cfg))

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    p_spec = jax.tree.map(spec, params)
    x_spec = jax.ShapeDtypeStruct((N, 32, 32, 3), jnp.float32,
                                  sharding=one_chip)

    # the engine's protocol: the static feature shape stays out of the
    # residuals a jitted program hands over (``CNNModel.pair``)
    feat_shape = cfg.feature_hw() + (cfg.channels[-1],)

    def forward(params, x):
        logits, res = cnn.forward_with_residuals(params, x, cfg, "guided",
                                                 precision)
        return logits, {k: v for k, v in res.items() if k != "feat_shape"}

    def replay(params, residuals, seeds):
        return cnn.backward_seeds(params, dict(residuals,
                                               feat_shape=feat_shape),
                                  seeds, cfg, "guided", precision)

    _, res = jax.eval_shape(forward, params, x_spec)
    seeds = jax.ShapeDtypeStruct((S, N, cfg.num_classes), jnp.float32,
                                 sharding=one_chip)
    names = {}
    for prog, fn, args in (
            ("forward", forward, (p_spec, x_spec)),
            ("replay", replay, (p_spec, jax.tree.map(spec, res), seeds))):
        text = jax.jit(fn).lower(*args).compile().as_text()
        names[prog] = re.findall(
            r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
            text)
        assert names[prog], prog
        for name in names[prog]:
            family = name.rsplit(".", 1)[0]
            assert family in KERNEL_FAMILIES, (prog, name)
    assert {n.rsplit(".", 1)[0] for n in names["forward"]} >= {
        "conv2d_fwd", "vmm_fwd", "maxpool_fwd", "relu_mask_fwd"}
    assert {n.rsplit(".", 1)[0] for n in names["replay"]} >= {
        "conv2d_bwd", "vmm_bwd"}
