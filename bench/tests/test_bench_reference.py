"""The plain float32 reference against the program's own forward pass,
at smoke size on the CPU, with the same bf16-drawn weights."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, weights


@pytest.mark.parametrize("arch", ["granite-3-2b", "yi-9b"])
def test_reference_matches_models_forward(arch):
    from repro.configs import get_smoke_config
    from repro.models import forward, model_specs
    from repro.models.params import is_spec

    c = dataclasses.replace(get_smoke_config(arch), n_layers=3)
    cfg = {"num_hidden_layers": c.n_layers, "hidden_size": c.d_model,
           "num_attention_heads": c.n_heads,
           "num_key_value_heads": c.n_kv_heads,
           "head_dim": c.resolved_head_dim, "intermediate_size": c.d_ff,
           "vocab_size": c.vocab_size,
           "tie_word_embeddings": c.tie_embeddings,
           "rms_norm_eps": c.norm_eps, "rope_theta": c.rope_theta}
    seed = 2**34 + 99
    drawn = weights.draw_params(cfg, seed, jnp.bfloat16,
                                vocab_rows=c.padded_vocab)
    shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                          model_specs(c), is_leaf=is_spec)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights.to_program(drawn, shapes))
    rng = np.random.default_rng(0)
    seqs = [list(rng.integers(4, 260, n)) for n in (37, 100)]
    want = [list(range(30, 37)), [0, 50, 99]]
    ref = reference.logits(cfg, seed, seqs, want, length=512, batch=3)
    with jax.default_matmul_precision("highest"):
        for s, w, r in zip(seqs, want, ref):
            lg, _ = forward(c, params, {"tokens": jnp.asarray([s])})
            got = np.asarray(lg[0])[w][:, :c.vocab_size]
            # float32 both sides; only summation order differs
            np.testing.assert_allclose(r, got, atol=1e-5, rtol=1e-5)


def test_leafwise_int8_matches_the_programs_quantization():
    """The int8 control's leaf-by-leaf quantization gives the tree the
    program's ``quantize_params`` gives, up to the float32 rounding of
    the scale that compiling ``quantize`` whole may change: a value at a
    rounding boundary may land one step off."""
    from repro.configs import get_smoke_config
    from repro.models import model_specs
    from repro.models.params import is_spec
    from repro.models.quant import QuantizedTensor, quantize_params

    from bench import run

    c = dataclasses.replace(get_smoke_config("yi-9b"), n_layers=2)
    cfg = {"num_hidden_layers": 2, "hidden_size": c.d_model,
           "num_attention_heads": c.n_heads,
           "num_key_value_heads": c.n_kv_heads,
           "head_dim": c.resolved_head_dim, "intermediate_size": c.d_ff,
           "vocab_size": c.vocab_size,
           "tie_word_embeddings": c.tie_embeddings}
    specs = model_specs(c)
    shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
                          specs, is_leaf=is_spec)

    def draw():
        return weights.to_program(weights.draw_params(
            cfg, 5, jnp.bfloat16, vocab_rows=c.padded_vocab), shapes)

    want = quantize_params(draw(), specs)
    got = run.quantize_leaves(draw(), specs)
    is_q = lambda x: isinstance(x, QuantizedTensor)
    assert any(is_q(x) for x in jax.tree.leaves(want, is_leaf=is_q))
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert a.dtype == b.dtype and a.shape == b.shape
        quantized = a.dtype == jnp.int8
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if quantized:
            assert np.abs(a - b).max() <= 1 and (a != b).mean() < 1e-3
        else:
            np.testing.assert_allclose(a, b, rtol=1e-6)
