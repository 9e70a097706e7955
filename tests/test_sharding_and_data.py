"""Sharding rules, tokenizer round-trips, loader determinism."""

import subprocess
import sys

import jax
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # dev-only dep; see requirements-dev.txt
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.data.loader import Prefetcher, pack_documents, synthetic_lm_batches
from repro.data.tokenizer import ByteTokenizer
from repro.sharding.logical import MeshContext, DEFAULT_RULES


class FakeDevices:
    shape = (4, 4)


class FakeMesh:
    axis_names = ("data", "model")
    devices = FakeDevices()


def _resolve(axes, rules=None):
    merged = dict(DEFAULT_RULES)
    merged.update(rules or {})
    ctx = MeshContext.__new__(MeshContext)
    ctx.mesh = FakeMesh()
    ctx.rules = merged
    return ctx.resolve(axes)


def test_rules_resolution_basics():
    assert _resolve(("batch", "seq", "embed")) == P("data", None, None)
    assert _resolve(("embed_fsdp", "mlp")) == P("data", "model")
    assert _resolve(("vocab", "embed")) == P("model", None)


def test_rules_drop_missing_mesh_axes():
    # "pod" doesn't exist on the single-pod mesh → silently dropped
    assert _resolve(("batch",)) == P("data")


def test_rules_never_reuse_a_mesh_axis():
    # both logical axes map to "model": the second use must be dropped
    spec = _resolve(("heads", "mlp"))
    used = [s for s in spec if s is not None]
    assert used.count("model") <= 1


def test_per_arch_overrides():
    spec = _resolve(("experts", "embed_fsdp", "expert_mlp"),
                    rules={"experts": None, "expert_mlp": "model"})
    assert spec == P(None, "data", "model")


def test_grok_overrides_merge_over_default_rules():
    """use_mesh(mesh, cfg.rules()) merges per-arch overrides on top of
    DEFAULT_RULES: grok moves `experts` off "model" and puts `expert_mlp`
    on it (8 experts can't tile a wide TP axis), while untouched defaults
    (heads → "model") survive the merge."""
    from repro.configs import get_smoke_config
    from repro.sharding.logical import mesh_active, use_mesh

    am = jax.sharding.AbstractMesh((32,), ("model",))
    grok_rules = get_smoke_config("grok-1-314b").rules()
    assert grok_rules == {"experts": None, "expert_mlp": "model"}
    assert not mesh_active()
    with use_mesh(am, grok_rules) as ctx:
        assert mesh_active()
        assert ctx.rules["experts"] is None
        assert ctx.rules["expert_mlp"] == "model"
        assert ctx.rules["heads"] == "model"  # default retained
        spec = ctx.resolve(("experts", "expert_mlp"), (8, 32768))
        assert spec == P(None, "model")
    assert not mesh_active()


def test_shard_is_noop_outside_mesh():
    from repro.sharding.logical import shard, use_mesh

    x = jax.numpy.ones((4, 8))
    assert shard(x, "batch", "embed") is x
    with pytest.raises(ValueError, match="rank mismatch"):
        with use_mesh(jax.sharding.AbstractMesh((2,), ("model",))):
            shard(x, "batch")


def test_abstract_mesh_resolution_matches_fake_mesh():
    """AbstractMesh exposes .shape as a name→size Mapping (no .devices);
    MeshContext.resolve must agree with the devices-backed path on both
    plain resolution and divisibility-driven axis dropping."""
    am = jax.sharding.AbstractMesh((4, 4), ("data", "model"))
    ctx = MeshContext(mesh=am, rules=dict(DEFAULT_RULES))
    for axes in [("batch", "seq", "embed"), ("embed_fsdp", "mlp"),
                 ("vocab", "embed")]:
        assert ctx.resolve(axes) == _resolve(axes)
    # 36 heads tile a 4-way axis; 30 don't → dropped to replication,
    # identically on both paths
    assert ctx.resolve(("heads",), (36,)) == P("model")
    assert ctx.resolve(("heads",), (30,)) == P(None)
    for n in (36, 30):
        assert ctx.resolve(("heads",), (n,)) == _resolve_shaped(("heads",), (n,))


def _resolve_shaped(axes, shape, rules=None):
    merged = dict(DEFAULT_RULES)
    merged.update(rules or {})
    ctx = MeshContext.__new__(MeshContext)
    ctx.mesh = FakeMesh()
    ctx.rules = merged
    return ctx.resolve(axes, shape)


# ---------------------------------------------------------------------------
# tokenizer / loader
# ---------------------------------------------------------------------------


@given(st.text(max_size=200))
@settings(max_examples=100, deadline=None)
def test_byte_tokenizer_roundtrip(text):
    tok = ByteTokenizer(512)
    assert tok.decode(tok.encode(text)) == text


def test_synthetic_batches_deterministic_and_resumable():
    a = synthetic_lm_batches(1000, 4, 16, seed=7)
    b = synthetic_lm_batches(1000, 4, 16, seed=7)
    first_a = [next(a) for _ in range(3)]
    first_b = [next(b) for _ in range(3)]
    for x, y in zip(first_a, first_b):
        np.testing.assert_array_equal(x, y)
    # resuming at step 2 reproduces the same batch (restart determinism)
    c = synthetic_lm_batches(1000, 4, 16, seed=7, start_step=2)
    np.testing.assert_array_equal(next(c), first_a[2])


def test_pack_documents():
    tok = ByteTokenizer(512)
    docs = ["hello world", "second document here", "third"]
    windows = pack_documents(docs, tok.encode, seq_len=8, eos_id=tok.eos_id)
    assert windows.ndim == 2 and windows.shape[1] == 8
    assert (windows >= 0).all() and (windows < 512).all()


def test_prefetcher_preserves_order():
    it = iter([np.full((2,), i) for i in range(5)])
    pf = Prefetcher(it, depth=2)
    got = [int(x[0]) for x in pf]
    assert got == [0, 1, 2, 3, 4]


def test_host_batch_slice():
    from repro.data.loader import host_batch_slice

    assert host_batch_slice(256, 3, 16) == (48, 64)
    with pytest.raises(ValueError):
        host_batch_slice(255, 0, 16)
