"""CPU checks of the benchmark's yardstick: FLOP and byte counts, the
trace reduction, traffic draws, pair crediting and the manifest."""

import json
import math
import re
from pathlib import Path

import pytest

from bench import flops, scenarios, trace, traffic, weights

ROOT = Path(__file__).resolve().parents[2]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
GRANITE = json.loads((ROOT / "bench/configs/granite-3-2b-nomult.json").read_text())
YI = json.loads((ROOT / "bench/configs/yi-9b-24l.json").read_text())


# ------------------------------------------------------------- FLOPs, bytes

@pytest.mark.parametrize("cfg, per_layer, layers", [
    # q 2*2048*2048 + k,v 2*2*2048*512 + o 2*2048*2048 + mlp 3*2*2048*8192
    (GRANITE, 8_388_608 + 4_194_304 + 8_388_608 + 100_663_296, 40),
    # q 2*4096*4096 + k,v 2*2*4096*512 + o 2*4096*4096 + mlp 3*2*4096*11008
    (YI, 33_554_432 + 8_388_608 + 33_554_432 + 270_532_608, 24),
])
def test_linear_flops_match_hand_count(cfg, per_layer, layers):
    assert flops.linear_flops_per_token(cfg) == per_layer * layers


def test_span_flops_counts_attention_at_each_position():
    cfg = GRANITE
    # positions 10..13 attend 11+12+13+14 = 50 keys; logits once
    want = (4 * flops.linear_flops_per_token(cfg)
            + 40 * 4 * 32 * 64 * 50 + 2 * 2048 * 49155)
    assert flops.span_flops(cfg, 10, 14) == want
    assert flops.span_flops(cfg, 5, 5) == 0
    assert flops.step_flops(cfg, [(10, 14), (0, 1)]) == (
        want + flops.span_flops(cfg, 0, 1))


@pytest.mark.parametrize("cfg, params, kv_per_token", [
    # 40 * (2048*2048*2 + 2*2048*512 + 3*2048*8192 + 2*2048)
    # + 49155*2048 + 2048; KV 40 layers * 2 * 8 heads * 64 * 2 bytes
    (GRANITE, 40 * 60_821_504 + 100_669_440 + 2048, 81_920),
    # 24 * (4096*4096*2 + 2*4096*512 + 3*4096*11008 + 2*4096)
    # + 2*64000*4096 + 4096; KV 24 * 2 * 4 * 128 * 2
    (YI, 24 * 173_023_232 + 524_288_000 + 4096, 49_152),
])
def test_params_and_kv_bytes_match_hand_count(cfg, params, kv_per_token):
    assert flops.param_count(cfg) == params
    assert flops.kv_bytes_per_token(cfg) == kv_per_token
    assert flops.decode_step_bytes(cfg, [100, 50]) == (
        2 * params + 150 * kv_per_token)


# --------------------------------------------------------- trace reduction

def test_union_gaps_and_total():
    busy = trace.union([(0, 10), (5, 12), (20, 30), (30, 31), (40, 45)])
    assert busy == [(0, 12), (20, 31), (40, 45)]
    assert trace.total(busy) == 28
    assert trace.gaps(busy) == [(12, 20), (31, 40)]


def test_gaps_are_labelled_by_the_overlapping_host_span():
    host = [(10, 18, "bench.prefill_rows"), (30, 33, "bench.decode_active"),
            (34, 39, "bench.insert_row")]
    got = trace.label_gaps([(12, 20), (31, 40), (50, 60)], host)
    assert got == {"bench.prefill_rows": 8, "bench.insert_row": 9,
                   "no engine call on the host": 10}


def test_decode_program_is_found_by_its_execution_count():
    mods = ([(i * 30, i * 30 + 20_000_000, "jit__lambda(1)")
             for i in range(13)]                       # 13 steps of 20 ms
            + [(0, 1_900_000_000, "jit__lambda(2)")] * 4   # 4 prefills
            + [(0, 10_000, "jit_broadcast_in_dim(3)")] * 12)
    progs = trace.programs(mods)
    assert progs["jit__lambda(1)"] == [13, pytest.approx(0.26)]
    assert trace.decode_program(progs, 13) == "jit__lambda(1)"
    assert trace.decode_program(progs, 5) == "jit__lambda(2)"
    assert trace.decode_program(progs, 0) is None


def test_op_names_lose_their_layouts():
    name = ("%fusion.130 = (f32[6,4096]{1,0:T(8,128)S(1)}, "
            "bf16[6,4096,4096]{1,2,0:T(8,128)(2,1)}) fusion(s32[]{:T(128)} "
            "%get-tuple-element.559), kind=kOutput")
    assert trace.short_name(name) == (
        "%fusion.130 = (f32[6,4096], bf16[6,4096,4096]) "
        "fusion(s32[] %get-tuple-element.559), kind=kOutput")
    assert len(trace.short_name("x" * 500)) == 200


def test_device_time_is_clipped_to_the_window():
    ev = [(0, 10, "a"), (5, 20, "b"), (25, 30, "c"), (40, 50, "d")]
    assert trace.clip(ev, 8, 28) == [(8, 10, "a"), (8, 20, "b"),
                                     (25, 28, "c")]


def test_kl_divergence_of_logits():
    import numpy as np

    from bench.check import kl_divergence

    ref = np.array([[0.0, 0.0], [2.0, 0.0]])
    assert np.allclose(kl_divergence(ref, ref), 0.0)
    assert np.allclose(kl_divergence(ref, ref + 5.0), 0.0)  # shift-free
    p = np.array([0.5, 0.5])
    q = np.exp([1.0, 0.0]) / np.exp([1.0, 0.0]).sum()
    want = (p * np.log(p / q)).sum()
    got = kl_divergence(ref[:1], np.array([[1.0, 0.0]]))
    assert np.allclose(got, want)


def test_summarize_without_a_trace_reports_nothing_measured(tmp_path):
    s = trace.summarize(tmp_path, 2.0)
    assert s["busy_s"] is None and s["programs"] == {}


# ------------------------------------------------------------------ traffic

MIX = traffic.load_mix("block")


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**40 + 3])
def test_query_draws_are_deterministic_per_seed(seed):
    for p in range(len(MIX["pipelines"])):
        a = traffic.make_query(MIX, seed, p, 1, 4096, traffic.byte_tokens)
        b = traffic.make_query(MIX, seed, p, 1, 4096, traffic.byte_tokens)
        assert (a.tables.r1, a.tables.r2, a.b1, a.b2) == (
            b.tables.r1, b.tables.r2, b.b1, b.b2)
    other = traffic.make_query(MIX, seed + 1, 2, 1, 4096, traffic.byte_tokens)
    assert other.tables.r1 != b.tables.r1


def test_every_seed_draws_the_same_sizes_and_selectivity():
    shapes = set()
    for seed in (1, 99, 2**33):
        for p, spec in enumerate(MIX["pipelines"]):
            q = traffic.make_query(MIX, seed, p, 0, 4096, traffic.byte_tokens)
            shapes.add((p, len(q.tables.r1), len(q.tables.r2),
                        len(q.tables.truth())))
            assert [len(q.tables.r1), len(q.tables.r2)] == spec["rows"]
    assert len(shapes) == len(MIX["pipelines"])


@pytest.mark.parametrize("mix", ["block", "block_nocache"])
@pytest.mark.parametrize("seed", range(12))
def test_planned_blocks_fit_the_window_with_their_answers(mix, seed):
    """No call of a mix overflows: prompt + answer fit 4096 positions."""
    from repro.core.prompts import block_prompt

    mix = traffic.load_mix(mix)
    for p in range(len(mix["pipelines"])):
        q = traffic.make_query(mix, 10**9 + seed, p, seed, 4096,
                               traffic.byte_tokens)
        t = q.tables
        for lo1 in range(0, len(t.r1), q.b1):
            for lo2 in range(0, len(t.r2), q.b2):
                b1, b2 = t.r1[lo1:lo1 + q.b1], t.r2[lo2:lo2 + q.b2]
                ans = "".join(f"{x},{y}; " for x, a in enumerate(b1, 1)
                              for y, b in enumerate(b2, 1)
                              if t.predicate(a, b)) + "Finished"
                used = traffic.byte_tokens(block_prompt(b1, b2, t.condition))
                assert used + len(ans) + 1 <= 4096


@pytest.mark.parametrize("name, rows", [("emails", (34, 10)),
                                        ("reviews", (6, 6)),
                                        ("ads", (16, 16))])
def test_scenario_tables_are_a_function_of_their_generator(name, rows):
    """The yardstick's own scenarios: one generator state gives the same
    tables and truth, and the truth is the predicate over every pair."""
    import random

    a = scenarios.make(name, random.Random(5), list(rows))
    b = scenarios.make(name, random.Random(5), list(rows))
    assert (a.r1, a.r2, a.condition, a.truth()) == (
        b.r1, b.r2, b.condition, b.truth())
    assert (len(a.r1), len(a.r2)) == rows
    assert a.truth() == {(i, k) for i in range(rows[0])
                         for k in range(rows[1])
                         if a.predicate(a.r1[i], a.r2[k])}


def test_unknown_operator_is_refused():
    with pytest.raises(ValueError, match="operator"):
        traffic.operator(dict(MIX, operator="nested_loop"))
    with pytest.raises(ValueError, match="operator"):
        traffic.make_query(dict(MIX, operator=None), 1, 0, 0, 4096,
                           traffic.byte_tokens)
    assert isinstance(traffic.operator(MIX), traffic.BlockJoin)


def test_tuple_join_calls_are_credited_one_pair_each():
    from bench.capture import Call

    mix = dict(MIX, operator="tuple_join")
    q = traffic.make_query(mix, 3, 0, 0, 4096, traffic.byte_tokens)
    n2 = len(q.tables.r2)
    assert traffic.operator(mix).calls(q) == len(q.tables.r1) * n2
    calls = [Call(0, 0, 0, 1.0, done=2.0, text="Yes"),
             Call(0, 0, n2 + 3, 1.0, done=3.0, text="No"),
             Call(0, 0, 5, 1.0)]  # never answered
    traffic.credit([q], calls)
    assert dict(q.memo) == {(0, 1, 0, 1): {(0, 0)},
                            (1, 2, 3, 4): set()}
    assert traffic.pairs_settled([q.memo], 0.0, 2.5) == 1
    assert traffic.pairs_settled([q.memo], 0.0, 3.0) == 2
    with pytest.raises(ValueError, match="scoring"):
        traffic.make_query(dict(mix, scoring=True), 3, 0, 0, 4096,
                           traffic.byte_tokens)


def test_call_latency_counts_unanswered_calls_at_the_close():
    from bench.capture import Call
    from bench.run import call_latencies_ms

    calls = [Call(0, 0, 0, submitted=0.5, done=1.5),   # warm-up: out
             Call(0, 1, 0, submitted=1.9, done=3.0),   # 1.1 s
             Call(0, 1, 1, submitted=2.0),             # queued: 8 s
             Call(0, 2, 0, submitted=9.0, done=12.0),  # still decoding: 1 s
             Call(0, 3, 0, submitted=10.5)]            # after the close: out
    got = call_latencies_ms(calls, 2.0, 10.0)
    assert got == pytest.approx([1100.0, 8000.0, 1000.0])


def test_pairs_are_credited_when_their_call_settles():
    memo = traffic.SettledMemo()
    memo[(0, 5, 0, 1)] = {(0, 0)}
    memo[(0, 16, 0, 16)] = set()
    t_a, t_b = memo.settled_at[(0, 5, 0, 1)], memo.settled_at[(0, 16, 0, 16)]
    assert traffic.rect_pairs((0, 16, 0, 16)) == 256
    assert traffic.pairs_settled([memo], t_a, t_b) == 5 + 256
    assert traffic.pairs_settled([memo], t_b + 1, t_b + 2) == 0
    memo[(0, 5, 0, 1)] = {(1, 0)}  # a re-answer keeps the first time
    assert memo.settled_at[(0, 5, 0, 1)] == t_a


def test_weights_slice_alone_equals_slice_in_tree():
    import jax
    import jax.numpy as jnp

    cfg = dict(GRANITE, num_hidden_layers=2, hidden_size=64,
               intermediate_size=96, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, vocab_size=300)
    seed = 2**35 + 17
    tree = weights.draw_params(cfg, seed, jnp.bfloat16, vocab_rows=304)
    key = weights.root_key(seed)
    one = jax.jit(lambda k: weights.draw_slice(
        cfg, k, "w_down", 1, jnp.bfloat16))(key)
    assert bool((one == tree["layers"]["w_down"][1]).all())
    assert tree["embed"].shape == (304, 64)
    assert float(abs(tree["embed"][300:]).max()) == 0.0


# ----------------------------------------------------------------- manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_manifest_keys_and_names():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51
    names = [c["name"] for c in MAN["configs"]] + [
        w["name"] for w in MAN["workloads"]] + [
        m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (ROOT / "bench/traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench/limits" / f"{w['name']}.json").is_file()
    assert "setup_s" in {m["name"] for m in MAN["end_to_end"]}
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in MAN["per_layer"]:
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in MAN["end_to_end"]}
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200


def test_configuration_files_hold_what_the_manifest_says():
    for c in MAN["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        for key in c["reduced"]:
            assert key in cfg
            assert not key.endswith(("_dim", "_rank", "_size"))


def test_every_cell_has_its_limits_and_every_metric_its_reader():
    import importlib

    for w in MAN["workloads"]:
        lim = json.loads((ROOT / "bench/limits" / f"{w['name']}.json")
                         .read_text())["limits"]
        assert lim["wrong_pairs"] == 0 and lim["unchecked_requests"] == 0
        assert math.isfinite(lim["logit_kl_mean"])
    for m in MAN["per_layer"]:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(mod.read)


def test_peak_table_is_keyed_by_device_kind():
    from bench import run

    assert run.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        run.load_peaks("cpu")


def test_program_config_matches_each_configuration_file():
    """Every width the program will run equals the configuration file's."""
    from bench import run

    for c in MAN["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        pc = run.program_config(cfg)
        assert pc.n_layers == cfg["num_hidden_layers"]
        assert cfg["serving"]["max_seq"] <= cfg["max_position_embeddings"]
        assert cfg["serving"]["pool_pages"] == (
            cfg["serving"]["slots"] * cfg["serving"]["max_seq"]
            // cfg["serving"]["page_size"])
