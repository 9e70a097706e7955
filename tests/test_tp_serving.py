"""Tensor-parallel + int8-resident serving (DESIGN.md §15).

Runs on ONE device wherever possible: the tp=1 serving mesh is a real
mesh (params committed, jits under ``use_mesh``, Pallas gates off) and
must be token-identical to the no-mesh baseline; per-shard residency of
the large dead configs is computed over ``jax.sharding.AbstractMesh``
with zero devices; and a subprocess leg forces 2 host devices to pin
TP=2 parity even in the default single-device tier-1 run.  In-process
multi-device tests activate under the CI ``tp`` job
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.data.tokenizer import ByteTokenizer
from repro.launch.mesh import make_serving_mesh
from repro.models import init_params, model_specs
from repro.models.params import is_spec
from repro.models.quant import (
    QuantizedTensor, abstract_quantized_params, deq, quantize,
    quantize_params, serving_param_shardings, shard_residency_bytes,
)
from repro.serve import Cluster, Engine
from repro.sharding.logical import (
    DEFAULT_RULES, MeshContext, mesh_active, shard, use_mesh,
)

KEY = jax.random.PRNGKey(7)
N_DEV = len(jax.devices())

GiB = 1024 ** 3
CHIP_BUDGET_GIB = 12.0  # v5e HBM minus KV/activation headroom (§15)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("granite-3-2b")
    params = init_params(model_specs(cfg), KEY, jnp.float32)
    tok = ByteTokenizer(cfg.vocab_size)
    return cfg, params, tok


def _engine(cfg, params, tok, **kw):
    kw.setdefault("max_seq", 256)
    kw.setdefault("slots", 2)
    return Engine(cfg, params, tok, **kw)


PROMPTS = ["short one", "a rather longer prompt with more tokens"]
EXPECTED = ["1,2; Finished", "none; Finished"]


def _gen(engine):
    return engine.generate(PROMPTS, max_tokens=10, stop="Finished",
                           expected=EXPECTED)


# ---------------------------------------------------------------------------
# tp=1 mesh ≡ no mesh (single device, always runs)
# ---------------------------------------------------------------------------


def test_tp1_mesh_engine_token_identical(setup):
    cfg, params, tok = setup
    base = _engine(cfg, params, tok)
    tp1 = _engine(cfg, params, tok, mesh=make_serving_mesh(tp=1))
    for a, b in zip(_gen(base), _gen(tp1)):
        assert a.text == b.text
        assert a.prompt_tokens == b.prompt_tokens
        assert a.cached_prompt_tokens == b.cached_prompt_tokens
        assert a.completion_tokens == b.completion_tokens


def test_tp1_mesh_score_and_embed_match(setup):
    cfg, params, tok = setup
    base = _engine(cfg, params, tok)
    tp1 = _engine(cfg, params, tok, mesh=make_serving_mesh(tp=1))
    sa = base.score_rows([("Q: yes?", " Yes"), ("Q: no?", " No")])
    sb = tp1.score_rows([("Q: yes?", " Yes"), ("Q: no?", " No")])
    for a, b in zip(sa, sb):
        assert a.logprob == pytest.approx(b.logprob, abs=1e-5)
    ea, la = base.embed_rows(["hello world"])
    eb, lb = tp1.embed_rows(["hello world"])
    assert la == lb
    np.testing.assert_allclose(ea, eb, atol=1e-5)


def test_quant_engine_serves_and_is_deterministic(setup):
    """int8 weights change logits (quality measured in the benchmark) but
    the engine must serve deterministically, and quantization must be
    idempotent (a cluster re-quantizing an already-quantized tree)."""
    cfg, params, tok = setup
    qp = quantize_params(params, model_specs(cfg))
    qp2 = quantize_params(qp, model_specs(cfg))
    for a, b in zip(jax.tree.leaves(qp), jax.tree.leaves(qp2)):
        assert a is b  # second pass is a no-op
    e1 = _engine(cfg, qp, tok, quant=True)   # already-quantized tree
    e2 = _engine(cfg, params, tok, quant=True)
    for a, b in zip(_gen(e1), _gen(e2)):
        assert a.text == b.text


# ---------------------------------------------------------------------------
# TP=2 parity pinned from the single-device tier-1 run via a subprocess
# ---------------------------------------------------------------------------

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.data.tokenizer import ByteTokenizer
from repro.models import init_params, model_specs
from repro.serve import Engine
from repro.launch.mesh import make_serving_mesh

cfg = get_smoke_config("granite-3-2b")
params = init_params(model_specs(cfg), jax.random.PRNGKey(7), jnp.float32)
tok = ByteTokenizer(cfg.vocab_size)
prompts = ["short one", "a rather longer prompt with more tokens"]
exp = ["1,2; Finished", "none; Finished"]
kw = dict(max_seq=256, slots=2)
base = Engine(cfg, params, tok, **kw)
a = base.generate(prompts, max_tokens=10, stop="Finished", expected=exp)
mesh = make_serving_mesh(jax.devices()[:2], tp=2)
tp2 = Engine(cfg, params, tok, mesh=mesh, **kw)
b = tp2.generate(prompts, max_tokens=10, stop="Finished", expected=exp)
for x, y in zip(a, b):
    assert x.text == y.text, (x.text, y.text)
    assert x.prompt_tokens == y.prompt_tokens
    assert x.completion_tokens == y.completion_tokens
print("TP2-PARITY-OK")
"""


def test_tp2_parity_subprocess(setup):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "TP2-PARITY-OK" in out.stdout


# ---------------------------------------------------------------------------
# In-process multi-device legs (CI tp job: 8 forced host devices)
# ---------------------------------------------------------------------------


@pytest.mark.skipif(N_DEV < 2, reason="needs >=2 XLA devices")
@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("prefix", [False, True])
def test_tp2_token_identical_all_cache_legs(setup, paged, prefix):
    cfg, params, tok = setup
    base = _engine(cfg, params, tok, paged=paged, prefix_cache=prefix)
    tp2 = _engine(cfg, params, tok, paged=paged, prefix_cache=prefix,
                  mesh=make_serving_mesh(jax.devices()[:2], tp=2))
    for a, b in zip(_gen(base), _gen(tp2)):
        assert a.text == b.text
        assert a.prompt_tokens == b.prompt_tokens
        assert a.cached_prompt_tokens == b.cached_prompt_tokens


@pytest.mark.skipif(N_DEV < 2, reason="needs >=2 XLA devices")
def test_tp2_quant_engine_serves(setup):
    cfg, params, tok = setup
    e = _engine(cfg, params, tok, quant=True,
                mesh=make_serving_mesh(jax.devices()[:2], tp=2))
    res = _gen(e)
    assert all(r.completion_tokens > 0 for r in res)


@pytest.mark.skipif(N_DEV < 4, reason="needs >=4 XLA devices")
def test_cluster_dp_x_tp(setup):
    """2 replicas x tp=2 over 4 devices: disjoint contiguous slices,
    token-identical joins, per-replica pools/caches isolated."""
    cfg, params, tok = setup
    base = _engine(cfg, params, tok)
    expect = [r.text for r in _gen(base)]
    with Cluster.replicate(cfg, params, tok, 2, tp=2,
                           max_seq=256, slots=2) as cl:
        meshes = [e.mesh for e in cl.engines]
        devs = [tuple(m.devices.flat) for m in meshes]
        assert len(devs[0]) == 2 and len(devs[1]) == 2
        assert not (set(devs[0]) & set(devs[1]))  # disjoint slices
        handles = [cl.submit(p, max_tokens=10, stop="Finished", expected=e)
                   for p, e in zip(PROMPTS, EXPECTED)]
        cl.drain()
        assert [h.result.text for h in handles] == expect


def test_replicate_rejects_undersized_device_set(setup):
    cfg, params, tok = setup
    with pytest.raises(ValueError, match="devices"):
        Cluster.replicate(cfg, params, tok, 2, tp=max(N_DEV, 2),
                          max_seq=256, slots=2)


# ---------------------------------------------------------------------------
# Dead-config residency smoke: AbstractMesh, zero devices
# ---------------------------------------------------------------------------

#: (arch, extra rule overrides, TP degree at which int8 fits and bf16
#: does not — the DESIGN.md §15 table)
RESIDENCY_CASES = [
    ("mistral-large-123b", {}, 16),
    ("grok-1-314b", {}, 64),
    ("jamba-1.5-large-398b", {"experts": None, "expert_mlp": "model"}, 32),
]


@pytest.mark.parametrize("arch,overrides,tp", RESIDENCY_CASES)
def test_large_config_int8_residency_fits_budget(arch, overrides, tp):
    cfg = get_config(arch)
    specs = model_specs(cfg)
    rules = dict(cfg.rules())
    rules.update(overrides)
    bf = shard_residency_bytes(specs, tp=tp, rules=rules, quant=False)
    q8 = shard_residency_bytes(specs, tp=tp, rules=rules, quant=True)
    assert q8 / GiB <= CHIP_BUDGET_GIB, (
        f"{arch}: int8 shard {q8 / GiB:.1f} GiB blew the "
        f"{CHIP_BUDGET_GIB} GiB budget at tp={tp}")
    assert bf / GiB > CHIP_BUDGET_GIB, (
        f"{arch}: bf16 unexpectedly fits at tp={tp} — tighten the table")
    # int8 must roughly halve residency (scales add back a little)
    assert q8 < 0.6 * bf


@pytest.mark.parametrize("arch,overrides,tp", RESIDENCY_CASES)
def test_abstract_quantized_tree_is_sharded_int8(arch, overrides, tp):
    cfg = get_config(arch)
    rules = dict(cfg.rules())
    rules.update(overrides)
    mesh = jax.sharding.AbstractMesh((tp,), ("model",))
    tree = abstract_quantized_params(model_specs(cfg), mesh, rules)
    leaves = jax.tree.leaves(tree)
    assert all(l.sharding is not None for l in leaves)
    n_q = sum(1 for l in leaves if l.dtype == jnp.int8)
    assert n_q > 0  # matmul weights went int8
    # at least one int8 payload actually shards over the model axis
    assert any(
        l.dtype == jnp.int8
        and l.sharding.shard_shape(l.shape) != tuple(l.shape)
        for l in leaves)


def test_jamba_needs_expert_override_at_tp32():
    """16 experts cannot tile a 32-way axis: without the grok-style
    expert_mlp override the expert weights replicate and per-shard
    bytes explode — the honest divisibility fallback, not an error."""
    cfg = get_config("jamba-1.5-large-398b")
    specs = model_specs(cfg)
    plain = shard_residency_bytes(specs, tp=32, rules=cfg.rules())
    over = dict(cfg.rules())
    over.update({"experts": None, "expert_mlp": "model"})
    fixed = shard_residency_bytes(specs, tp=32, rules=over)
    assert plain > 4 * fixed


def test_serving_param_shardings_matches_quantized_tree(setup):
    cfg, params, tok = setup
    qp = quantize_params(params, model_specs(cfg))
    mesh = make_serving_mesh(tp=1)
    sh = serving_param_shardings(qp, model_specs(cfg), mesh)
    # leaf-for-leaf structural match → device_put(params, sh) is valid
    assert (jax.tree.structure(qp) == jax.tree.structure(sh))
    placed = jax.device_put(qp, sh)
    for a, b in zip(jax.tree.leaves(qp), jax.tree.leaves(placed)):
        assert a.shape == b.shape and a.dtype == b.dtype


# ---------------------------------------------------------------------------
# quant.deq dtype + per-channel round-trip (satellite b, hypothesis-free)
# ---------------------------------------------------------------------------


def test_deq_default_preserves_scale_dtype():
    w = jax.random.normal(KEY, (16, 8), jnp.float32)
    qt = quantize(w)
    assert deq(qt).dtype == jnp.float32        # no silent bf16 downcast
    assert deq(qt, jnp.bfloat16).dtype == jnp.bfloat16
    assert deq(qt, jnp.float16).dtype == jnp.float16
    x = jnp.ones((4, 4), jnp.bfloat16)
    assert deq(x) is x                         # unquantized passthrough


def test_quantize_roundtrip_error_bounded_per_channel():
    # wildly different per-channel magnitudes: a global scale would
    # destroy the small channels, per-channel keeps each bounded
    mags = jnp.array([1e-3, 1.0, 50.0, 1e3])
    w = jax.random.normal(KEY, (64, 4), jnp.float32) * mags[None, :]
    qt = quantize(w)
    err = jnp.abs(deq(qt) - w)
    amax = jnp.max(jnp.abs(w), axis=0)
    # symmetric int8: per-channel |error| <= half a quantization step
    assert bool(jnp.all(err <= amax[None, :] / 127.0 * 0.5 + 1e-9))


# ---------------------------------------------------------------------------
# sharding/logical override merging + no-op guarantees (satellite c)
# ---------------------------------------------------------------------------


def test_grok_overrides_merge_over_default_rules():
    cfg = get_config("grok-1-314b")
    rules = cfg.rules()
    assert rules["experts"] is None          # 8 experts on a 16-way axis
    assert rules["expert_mlp"] == "model"    # TP the expert FFN dim instead
    mesh = jax.sharding.AbstractMesh((16,), ("model",))
    with use_mesh(mesh, rules) as ctx:
        assert ctx.rules["expert_mlp"] == "model"      # override applied
        assert ctx.rules["experts"] is None
        assert ctx.rules["heads"] == DEFAULT_RULES["heads"]  # rest intact
        spec = ctx.resolve(("experts", "expert_mlp"), shape=(8, 32768))
        assert tuple(spec) == (None, "model")


def test_shard_is_noop_outside_mesh():
    assert not mesh_active()
    x = jnp.ones((4, 8))
    assert shard(x, "batch", "embed") is x   # the exact same object
    assert not mesh_active()


def test_mesh_active_inside_context_only():
    mesh = make_serving_mesh(tp=1)
    assert not mesh_active()
    with use_mesh(mesh):
        assert mesh_active()
    assert not mesh_active()


def test_abstract_mesh_resolution_matches_real_mesh():
    """MeshContext.resolve reads sizes from AbstractMesh.shape — the
    residency math must agree with a real mesh of the same shape."""
    am = jax.sharding.AbstractMesh((1,), ("model",))
    rm = make_serving_mesh(tp=1)
    a = MeshContext(mesh=am, rules=dict(DEFAULT_RULES))
    r = MeshContext(mesh=rm, rules=dict(DEFAULT_RULES))
    for axes, shp in [(("embed_fsdp", "heads", "head_dim"), (64, 4, 16)),
                      (("batch", "kv_seq", None), (2, 128, 8))]:
        assert tuple(a.resolve(axes, shp)) == tuple(r.resolve(axes, shp))


def test_make_serving_mesh_validation():
    with pytest.raises(ValueError, match="tp must be >= 1"):
        make_serving_mesh(tp=0)
    with pytest.raises(ValueError, match="exactly tp"):
        make_serving_mesh(jax.devices()[:1], tp=2)
    m = make_serving_mesh(tp=1)
    assert m.axis_names == ("model",)
    assert m.devices.shape == (1,)
