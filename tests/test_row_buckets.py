"""A refill's prefill runs at a row bucket, not at ``slots`` rows.

``Engine.prefill_rows`` pads its batch to the smallest row bucket that
holds the prompts (the powers of two below ``slots``, then ``slots``).
The rows it computes must match today's slots-padded launch, and the
first launch at a sequence bucket compiles every row bucket of it, so
no later refill at that bucket compiles anything.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data.tokenizer import ByteTokenizer
from repro.launch.mesh import make_serving_mesh
from repro.models import init_params, model_specs
from repro.serve import Engine

KEY = jax.random.PRNGKey(5)
SLOTS = 4
LENS = (90, 30, 120, 61)   # one bucket (128) for every row count


@pytest.fixture(scope="module")
def params():
    cfg = get_smoke_config("granite-3-2b")
    return cfg, init_params(model_specs(cfg), KEY, jnp.float32)


def engine(params, *, paged, slots=SLOTS, row_buckets=None, mesh=None):
    cfg, p = params
    eng = Engine(cfg, p, ByteTokenizer(cfg.vocab_size), max_seq=256,
                 slots=slots, prefill_buckets=(128, 256), paged=paged,
                 prefix_cache=False, spec_decode=False, mesh=mesh)
    if row_buckets is not None:
        eng.row_buckets = row_buckets
    return eng


def prompts(n):
    return [f"row {r}: " + "x" * (LENS[r] - 8) for r in range(n)]


def refill_and_step(eng, texts):
    """Prefill ``texts`` into slots 0.., then one decode step of the
    greedy tokens; the prefill's logits, the tokens picked from them,
    and the decode step's logits, of the prompts' rows."""
    n = len(texts)
    state = eng.init_state()
    cache, logits, _, _ = eng.prefill_rows(texts)
    assert logits.shape[0] == eng.slots        # slots-wide at any row bucket
    for r in range(n):
        eng.insert_row(state, cache, logits, r, r)
    first = np.asarray(state.logits)
    toks = np.argmax(first, axis=-1).astype(np.int32)
    active = np.arange(eng.slots) < n
    eng.decode_active(state, toks, active)
    after = np.asarray(state.logits)
    eng.release_state(state)
    return first[:n], toks[:n], after[:n]


@pytest.mark.parametrize("slots,want", [(4, [1, 2, 4]), (6, [1, 2, 4, 6]),
                                        (8, [1, 2, 4, 8])])
def test_row_buckets_are_powers_of_two_below_slots_then_slots(params, slots,
                                                             want):
    assert engine(params, paged=True, slots=slots).row_buckets == want


@pytest.mark.parametrize("n", [1, 3, SLOTS])
@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_row_bucket_prefill_matches_the_slots_padded_one(params, paged, n):
    eng = engine(params, paged=paged)
    padded = engine(params, paged=paged, row_buckets=[SLOTS])
    texts = prompts(n)
    first, toks, after = refill_and_step(eng, texts)
    want_first, want_toks, want_after = refill_and_step(padded, texts)
    assert eng.prefill_positions_run - (0 if paged else SLOTS * 128) == (
        {1: 1, 3: 4, SLOTS: SLOTS}[n] * 128)
    np.testing.assert_allclose(first, want_first, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(toks, want_toks)
    np.testing.assert_allclose(after, want_after, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.argmax(after, -1),
                                  np.argmax(want_after, -1))


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_other_row_counts_of_a_met_bucket_compile_nothing(params, paged):
    eng = engine(params, paged=paged)
    refill_and_step(eng, prompts(1))      # meets bucket 128: compiles
    compiles = []

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for n in (2, 3, SLOTS, 1):
            refill_and_step(eng, prompts(n))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []


def test_dense_engine_on_a_mesh_serves_each_row_bucket(params):
    """A dense engine on a mesh compiles its row inserts on demand and
    serves 1, 3 and 4 prompts as it does without a mesh."""
    eng = engine(params, paged=False, mesh=make_serving_mesh(tp=1))
    plain = engine(params, paged=False)
    for n in (1, 3, SLOTS):
        got = refill_and_step(eng, prompts(n))
        want = refill_and_step(plain, prompts(n))
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
