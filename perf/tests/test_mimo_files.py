"""The window/global routed-expert family's benchmark files: the counts
against the tree, the new readers on a trace small enough to work by
hand, the cell's runner rehearsed at toy sizes, and the control."""

import json
import os

import numpy as np
import pytest

from perf import (harness, mimo_counts as mc, mimo_readers as mr,
                  mimo_rehearsal, mimo_weights as mw)

CONFIG, CELL = "mimo-v2-flash-ep16", "serve-mimo-closed64-mixed"
SEED = 2_345_678_901  # above 2**31, as the driver's are
MS = 1_000_000


@pytest.fixture(scope="module")
def sizes():
    return mw.load_sizes(CONFIG)


def test_counts_equal_the_tree_and_the_file(sizes):
    import jax

    with open(os.path.join(harness.HERE, "configs", f"{CONFIG}.json")) as f:
        cfg = json.load(f)
    tree = jax.eval_shape(lambda: mw.make_weights(1, sizes))
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert leaves == mc.num_params(sizes) == cfg["parameters"] \
        == 3_429_955_392
    assert all(a.dtype == "bfloat16" for a in jax.tree.leaves(tree))
    from ddl_tpu.models import hybrid

    assert hybrid.NAMED_SPECS[CONFIG].num_params == leaves
    assert mc.expert_params(sizes) * 2 == 50_331_648
    assert mc.kv_row_bytes(sizes, 0) == 5_120
    assert mc.kv_row_bytes(sizes, 1) == 25_600
    assert mc.expected_assignments(sizes, 64) == 64 * 6 * 0.5


def test_config_file_states_the_cut(sizes):
    with open(os.path.join(harness.HERE, "configs", f"{CONFIG}.json")) as f:
        cfg = json.load(f)
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "MiMo-V2-Flash")
        for key, value in row["config"].items():
            want = cfg["published"][key] if key in cfg["reduced"] else cfg[key]
            assert want == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_layer_pattern",
                              "moe_layer_freq", "n_routed_experts",
                              "vocab_size"]
    assert set(cfg["assumed"]) >= {"value_scale", "rotary", "window", "mtp"}
    assert sizes.rotary_dim == 64 and sizes.experts_held == (0, 16)
    assert sizes.layer_kinds == (0, 1, 1, 1, 1, 1, 0)


def test_traffic_is_the_issues(sizes):
    t = harness.load_json("traffic", "closed64-mixed")
    assert (t["kind"], t["clients"], t["block"], t["order_seed"]) == (
        "closed_loop", 64, 64, 0)
    assert t["prompt"] == {"dist": "lognormal", "median": 1024, "sigma": 1.0,
                           "min": 128, "max": 8192}
    assert t["output"] == {"dist": "lognormal", "median": 256, "sigma": 0.6,
                           "min": 64, "max": 1024}
    e = harness.load_cell(CELL)["engine"]
    assert e["capacity"] == 8192 + 1024 and e["page_size"] == 64


def test_flops_and_bytes_by_hand(sizes):
    e = sizes.d_model
    fixed = mc.fixed_matmul_params(sizes)
    attn = 2 * (e * 64 * 192 + 64 * 128 * e) + 2 * e * 4 * 320 \
        + 5 * e * 8 * 320 + 5 * (e * 64 * 192 + 64 * 128 * e)
    assert fixed == attn + 3 * e * 16384 + 6 * e * 256 + e * 19072
    # one decoded token at context 1000: 2 global layers see 1000 keys,
    # 5 window layers 128
    one = mc.serve_flops(sizes, [], [1000], assigned=3)
    assert one == 2 * fixed + 2 * 3 * 25_165_824 \
        + 2 * 64 * 320 * (2 * 1000 + 5 * 128)
    # a prompt of 200: causal pairs, and 128 * 129 / 2 + 72 * 128 in a window
    assert mc.prefill_pairs(sizes, 200) == (20_100, 8_256 + 9_216)
    assert mc.serve_flops(sizes, [10], []) == pytest.approx(
        2 * 10 * fixed + 2 * 30 * 25_165_824 + 2 * 64 * 320 * 7 * 55)
    weights = 2 * (mc.num_params(sizes) - 19072 * e
                   - 6 * 16 * 25_165_824)
    assert mc.decode_tick_bytes(sizes, [1000, 50], 14) == weights \
        + 14 * 50_331_648 + 1050 * 5_120 + (128 + 50) * 25_600


def ev(name, start_ms, dur_ms, **attrs):
    return [name, int(start_ms * MS), int(dur_ms * MS), attrs]


def sample():
    """Two decode ticks and a prefill in a window of 100 ms; the decode
    programs run 20 and 25 ms on the device."""
    host = [ev("perf_window", 0, 100),
            ev("serve.tick", 2, 30),
            ev("serve.decode", 4, 26, pages=16, moe_assigned=30,
               moe_touched=12, win_pages=6),
            ev("serve.tick", 40, 55),
            ev("serve.prefill", 41, 20, req=7, n=300, bucket=512,
               moe_assigned=900),
            ev("serve.decode", 62, 30, pages=16, moe_assigned=18,
               moe_touched=12, win_pages=8)]
    modules = [["jit_run_decode_p16(3)", 5 * MS, 20 * MS],
               ["jit_run_prefill_b512(4)", 42 * MS, 15 * MS],
               ["jit_run_decode_p16(3)", 63 * MS, 25 * MS]]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops",
             "events": [["fusion.1", m[1], m[2]] for m in modules]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]}]}
    calls = [{"kind": "decode", "contexts": [1000, 50], "resident_tokens": 1050},
             {"kind": "prefill", "tokens": 300, "req": 7},
             {"kind": "decode", "contexts": [1001, 51, 301],
              "resident_tokens": 1353}]
    return trace, calls


def context(sizes, trace, calls):
    cell = harness.load_cell(CELL)
    return {"span_trace": trace, "trace": trace, "cell": cell, "sizes": sizes,
            "facts": {"traced_calls": calls},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def test_readers_on_a_sample_by_hand(sizes):
    trace, calls = sample()
    ctx = context(sizes, trace, calls)
    assert mr.moe_tokens_per_expert(ctx, {}) == (30 / 12 + 18 / 12) / 2
    # 6 pages x 64 rows over 2 slots, 8 x 64 over 3: the median of two
    assert mr.window_rows_per_slot(ctx, {}) == (192 + 512 / 3) / 2
    shares = [mc.decode_tick_bytes(sizes, c, 12) / 819e9 / s
              for c, s in (([1000, 50], 0.020), ([1001, 51, 301], 0.025))]
    assert mr.decode_hbm_roofline(ctx, {}) == pytest.approx(
        100 * sum(shares) / 2)
    flops = mc.serve_flops(sizes, [300], [1000, 50, 1001, 51, 301],
                           30 + 900 + 18)
    assert mr.serve_mfu(ctx, {}) == pytest.approx(
        100 * flops / 0.100 / 197e12)
    assert 0 < mr.decode_hbm_roofline(ctx, {}) < 100


def test_readers_find_nothing_without_the_counters(sizes):
    trace, calls = sample()
    for e in trace["planes"][1]["lines"][0]["events"]:
        for key in ("moe_assigned", "moe_touched", "win_pages"):
            e[3].pop(key, None)
    ctx = context(sizes, trace, calls)
    assert mr.moe_tokens_per_expert(ctx, {}) is None
    assert mr.window_rows_per_slot(ctx, {}) is None
    assert mr.decode_hbm_roofline(ctx, {}) is None
    # the whole step's share falls back to the expected assignments
    flops = mc.serve_flops(sizes, [300], [1000, 50, 1001, 51, 301])
    assert mr.serve_mfu(ctx, {}) == pytest.approx(
        100 * flops / 0.100 / 197e12)


@pytest.fixture(scope="module")
def rehearsed():
    return mimo_rehearsal.run_cell(CELL, SEED, 1.5, False)


def test_cell_rehearses_correct(rehearsed):
    assert rehearsed["correct"], rehearsed["checked"]
    assert set(rehearsed["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                         "ttft_p50_ms", "itl_p95_ms"}
    assert rehearsed["failed"] == 0 and rehearsed["attempted"] > 4
    assert rehearsed["checked"]["compiles_in_window"]["value"] == 0


def test_traced_rehearsal_reads_the_counters():
    res = mimo_rehearsal.run_cell(CELL, SEED + 1, 1.5, True)
    assert res["correct"], res["checked"]
    # no device plane on the CPU: the span readers answer, the device
    # readers find nothing and are left out, none raises
    assert 1.0 <= res["metrics"]["moe_tokens_per_expert"]["value"] <= 12
    assert "decode_hbm_roofline.mimo" not in res["metrics"]


def test_control_fails_the_rehearsal_limits():
    """The fp8 reference's own first choices, put in the served tokens'
    place, come out of the cell's comparison as not correct: the
    rehearsal holds the two numbers the committed cell holds."""
    from perf import compare, serve_mimo_runner as runner

    cell, tiny = mimo_rehearsal.shrink(harness.load_cell(CELL))
    limits = cell["check"]["limits"]
    assert list(limits) == list(harness.load_cell(CELL)["check"]["limits"])
    rng = np.random.default_rng(5)
    served = [(rng.integers(0, tiny.vocab, 40).astype(np.int32),
               rng.integers(0, tiny.vocab, 16).astype(np.int32))
              for _ in range(3)]
    got = runner.reference_gaps(cell, tiny, SEED, served, control=True)
    checked = compare.checked_from(
        dict(got["control"], requests_failed=0, compiles_in_window=0), limits)
    assert not harness.judge(checked), checked
    assert all(e["value"] <= e["limit"] for k, e in checked.items()
               if not k.startswith("logit_gap"))


def test_limits_tool_judges_program_and_control(capsys):
    """``perf/mimo_limits.py`` puts the program's and the control's
    numbers through ``compare.checked_from`` with the cell's limits: the
    program correct, the control not, on every seed."""
    from perf import mimo_limits

    rc = mimo_limits.main(["--workload", CELL, "--seeds", "2",
                           "--control-seeds", "2", "--seconds", "1.0",
                           "--first-seed", str(SEED), "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["separated"]
    assert set(line["limits"]) >= {"logit_gap_mean", "logit_gap_p99"}
    assert all(r["correct"] and not r["over"]
               for r in line["readings"]["program"].values())
    control = line["readings"]["control_fp8"]
    assert len(control) == 2
    assert all(not r["correct"] and r["over"] for r in control.values())


def test_routing_counters_equal_the_references_count():
    """``decode_hbm_roofline.mimo`` prices a tick by the program's own
    ``moe_touched``: the counters of a prefill and of a decode tick equal
    what the reference's router picks for the same tokens, counted here."""
    import dataclasses

    import jax.numpy as jnp
    from ddl_tpu.serve import ServeConfig, engine_cls
    from perf import mimo_reference as ref, serve_mimo_runner as runner

    tiny = dataclasses.replace(mimo_rehearsal.TINY, experts_held=(4, 12))
    weights = mw.make_weights(SEED, tiny, "float32")
    spec = runner.spec_of(tiny)
    eng = engine_cls(spec)(ServeConfig(spec=spec, slots=3, capacity=64,
                                       page_size=4), params=weights)
    rng = np.random.default_rng(2)
    seqs = [list(rng.integers(0, tiny.vocab, n)) for n in (5, 19, 11)]

    def held(picks):  # [routed layers, tokens, top_k] -> assigned, touched
        inside = (picks >= tiny.experts_held[0]) & (picks < tiny.experts_held[1])
        return int(inside.sum()), sum(len(set(layer[mask].tolist()))
                                      for layer, mask in zip(picks, inside))

    def choices(seq):
        pad = np.zeros(-(-len(seq) // 8) * 8, np.int32)
        pad[:len(seq)] = seq
        return np.asarray(ref.routed_choices(
            weights, jnp.asarray(pad), sizes=tiny))[:, :len(seq)]

    for slot, seq in enumerate(seqs):
        tok, _ = eng.prefill(np.asarray(seq, np.int32), slot=slot,
                             request_id=slot)
        assert eng.last_counters["moe_assigned"] == held(choices(seq))[0]
        seq.append(tok)
    lengths = np.asarray([len(s) - 1 for s in seqs], np.int32)
    eng.decode(np.asarray([s[-1] for s in seqs], np.int32), lengths,
               np.arange(3, dtype=np.int32), np.ones(3, bool))
    tick = np.stack([choices(s)[:, -1] for s in seqs], axis=1)
    assigned, touched = held(tick)
    assert 0 < touched < assigned < 3 * 3 * tiny.top_k
    assert eng.last_counters["moe_assigned"] == assigned
    assert eng.last_counters["moe_touched"] == touched
