"""Model-zoo registry tests (CPU-cheap half).

Reference parity: ``examples/imagenet/models/{alex,googlenet,...}.py`` [uv]
(SURVEY.md §2.9) — the reference's ImageNet example accepted a zoo of archs.

Numerical init/forward/train coverage for the big convnets is NOT run here:
XLA:CPU takes minutes to compile a single AlexNet init, while the real
chip compiles it in seconds — the split the reference used (``@attr.gpu``
tests ran only where a GPU existed, SURVEY.md §4).  On the chip,
``chip_smoke.py`` trains ResNet-50 end to end; the rest of the zoo has no
on-chip check yet.
"""

from chainermn_tpu.models import AlexNet, GoogLeNet, VGG16
from chainermn_tpu.models.resnet import ARCHS


def test_zoo_registered_in_archs():
    assert ARCHS["alex"] is AlexNet
    assert ARCHS["alexnet"] is AlexNet
    assert ARCHS["googlenet"] is GoogLeNet
    assert ARCHS["vgg16"] is VGG16


def test_zoo_constructible_with_standard_knobs():
    for cls in (AlexNet, GoogLeNet, VGG16):
        m = cls(num_classes=10, stem_strides=1)
        assert m.num_classes == 10
        assert m.dropout_rate == 0.0  # step builders thread no dropout rng


def test_vit_registered_in_archs():
    from chainermn_tpu.models import ViT_B16, ViT_S16, ViT_Ti16

    assert ARCHS["vit_ti16"] is ViT_Ti16
    assert ARCHS["vit_s16"] is ViT_S16
    assert ARCHS["vit_b16"] is ViT_B16


def test_vit_forward_tiny():
    """A 2-layer ViT forward on tiny inputs is CPU-cheap (pure matmuls, no
    giant conv compiles) — init + forward + a grad step run here, unlike the
    convnet zoo, whose numerics need the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models import ViT

    m = ViT(num_classes=7, patch=4, d_model=32, depth=2, num_heads=4,
            dtype=jnp.float32)
    x = np.random.RandomState(0).randn(2, 16, 16, 3).astype(np.float32)
    variables = m.init(jax.random.PRNGKey(0), jnp.asarray(x), train=False)
    logits = m.apply(variables, x, train=False)
    assert logits.shape == (2, 7)
    assert logits.dtype == jnp.float32

    def loss(params):
        out = m.apply({"params": params}, x, train=True)
        return (out ** 2).mean()

    g = jax.grad(loss)(variables["params"])
    flat = jax.tree_util.tree_leaves(g)
    assert all(np.isfinite(np.asarray(l)).all() for l in flat)
    # cls token + pos embed exist and receive gradient
    assert float(np.abs(np.asarray(g["pos_embed"])).sum()) > 0


def test_vit_flash_attn_matches_xla():
    """attn_impl='flash' (interpret mode off-TPU) must match the einsum
    path numerically."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models import ViT

    kw = dict(num_classes=5, patch=4, d_model=32, depth=1, num_heads=2,
              dtype=jnp.float32)
    x = np.random.RandomState(1).randn(2, 16, 16, 3).astype(np.float32)
    m_x = ViT(attn_impl="xla", **kw)
    m_f = ViT(attn_impl="flash", **kw)
    variables = m_x.init(jax.random.PRNGKey(0), jnp.asarray(x), train=False)
    got_x = np.asarray(m_x.apply(variables, x, train=False))
    got_f = np.asarray(m_f.apply(variables, x, train=False))
    np.testing.assert_allclose(got_f, got_x, rtol=2e-4, atol=2e-4)
