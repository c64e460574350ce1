"""Regenerate the golden attribution heatmaps (tests/golden/*.npz).

Run from the repo root after an INTENTIONAL numeric change, then commit the
updated file together with the change that justified it:

    PYTHONPATH=src python tests/golden/generate.py

``test_golden.py`` recomputes the same fixed-seed heatmaps and asserts an
EXACT match against the stored arrays, so unintentional kernel-refactor
drift fails loudly.  Keep the model tiny: the point is a tripwire, not
coverage.

Parameters and input come from NumPy's ``RandomState`` (a stream NumPy
keeps stable across releases), not ``jax.random``, whose bits have changed
between JAX versions: the tripwire must fire on kernel changes, not on a
JAX upgrade.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from repro.core import attribution                            # noqa: E402
from repro.models import cnn                                  # noqa: E402

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "cnn_heatmaps.npz")

# tiny fixed config — small arrays, fast interpret-mode kernels
CFG = cnn.CNNConfig(in_hw=(8, 8), in_ch=3, channels=(4, 4), kernel=3,
                    fc=(16,), num_classes=4)
METHODS = ("saliency", "deconvnet", "guided")
PRECISIONS = ("f32", "fxp16")


def fixed_inputs():
    """He-scaled params (zero biases, the ``cnn.init`` layout) and a
    [1, 8, 8, 3] input, drawn from ``RandomState(0)``."""
    rng = np.random.RandomState(0)

    def leaf(path, a):
        if path[-1].key == "b":
            return jnp.zeros(a.shape, a.dtype)
        fan_in = int(np.prod(a.shape[:-1]))
        w = rng.standard_normal(a.shape) * np.sqrt(2.0 / fan_in)
        return jnp.asarray(w, a.dtype)

    shapes = jax.eval_shape(lambda: cnn.init(jax.random.PRNGKey(0), CFG))
    params = jax.tree_util.tree_map_with_path(leaf, shapes)
    x = jnp.asarray(rng.standard_normal((1, 8, 8, 3)), jnp.float32)
    return params, x


def compute_heatmaps():
    """{method_precision: [8, 8] f32 heatmap} for the fixed inputs."""
    params, x = fixed_inputs()
    out = {}
    for method in METHODS:
        for precision in PRECISIONS:
            fwd, bwd = cnn.seed_batched_attribution_jittable(
                params, CFG, method, precision)
            logits, res = jax.jit(fwd)(x)
            seeds = jax.nn.one_hot(jnp.argmax(logits, axis=-1),
                                   CFG.num_classes)
            rel = jax.jit(bwd)(res, seeds[None])
            out[f"{method}_{precision}"] = np.asarray(
                attribution.heatmap(rel[0])[0], np.float32)
    return out


if __name__ == "__main__":
    arrays = compute_heatmaps()
    np.savez(GOLDEN_PATH, **arrays)
    print(f"wrote {GOLDEN_PATH}: "
          + ", ".join(f"{k}{v.shape}" for k, v in sorted(arrays.items())))
