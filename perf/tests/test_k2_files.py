"""The latent-attention routed-expert block's benchmark files: the
counts against the tree and the issue's table, the configuration against
the catalog, the new readers on a trace small enough to work by hand,
the cell's runner rehearsed at toy sizes, and the control."""

import json
import os

import numpy as np
import pytest

from perf import (harness, k2_counts as kc, k2_readers as kr, k2_rehearsal,
                  k2_weights as kw, mimo_readers as mr)

CONFIG, CELL = "kimi-k2-ep32", "serve-k2-closed64-long"
SEED = 2_345_678_901  # above 2**31, as the driver's are
MS = 1_000_000


@pytest.fixture(scope="module")
def sizes():
    return kw.load_sizes(CONFIG)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(harness.HERE, "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


def test_counts_equal_the_tree_the_file_and_the_table(sizes, cfg):
    import jax

    tree = jax.eval_shape(lambda: kw.make_weights(1, sizes))
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert leaves == kc.num_params(sizes) == cfg["parameters"] \
        == 3_496_763_904
    assert all(a.dtype == "bfloat16" for a in jax.tree.leaves(tree))
    from ddl_tpu.models import hybrid
    from perf.serve_k2_runner import spec_of

    assert hybrid.NAMED_SPECS[CONFIG] == spec_of(sizes)
    assert hybrid.NAMED_SPECS[CONFIG].num_params == leaves
    # the issue's table, by part
    attn = 7168 * 1536 + 1536 + 1536 * 12288 + 7168 * 576 + 512 \
        + 512 * 16384 + 8192 * 7168
    assert attn == 101_124_096
    layer = lambda i: sum(int(np.prod(s))
                          for s in kw.block_shapes(sizes, i).values())
    assert layer(0) == attn + 14_336 + 3 * 7168 * 18432 == 497_500_160
    assert layer(1) == 147_931_520 + 12 * 44_040_192 == 676_413_824
    assert 2 * 20480 * 7168 + 7168 == 293_608_448
    assert kc.expert_params(sizes) * 2 == 88_080_384
    assert kc.latent_row_bytes(sizes) == 5_760
    assert kc.expected_assignments(sizes, 64) == 64 * 4 * 8 * 12 / 384


def test_config_file_states_the_cut(sizes, cfg):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Kimi-K2-Instruct")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            want = cfg["published"][key] if key in cfg["reduced"] else cfg[key]
            assert want == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 12, 20480)
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 384, "vocab_size": 163840}
    assert cfg["deployment"]["chips_sharing_a_layer"] == 32
    assert cfg["deployment"]["router_width"] == 384
    assert set(cfg["assumed"]) >= {"rotary_pairing", "scale", "router",
                                   "cached", "weights"}
    assert sizes.experts_held == (0, 12) and sizes.ffn_kinds == (0, 1, 1, 1, 1)
    assert (sizes.d_model, sizes.num_heads, sizes.q_lora, sizes.kv_lora,
            sizes.nope_dim, sizes.rope_dim, sizes.v_head_dim, sizes.d_ff,
            sizes.expert_ff, sizes.shared_ff, sizes.top_k, sizes.route_scale) \
        == (7168, 64, 1536, 512, 128, 64, 128, 18432, 2048, 2048, 8, 2.827)
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_traffic_is_the_issues(sizes):
    from perf import traffic

    t = harness.load_json("traffic", "closed64-long")
    assert (t["kind"], t["clients"], t["block"], t["order_seed"]) == (
        "closed_loop", 64, 64, 0)
    assert t["prompt"] == {"dist": "lognormal", "median": 2048, "sigma": 1.0,
                           "min": 256, "max": 16384}
    assert t["output"] == {"dist": "lognormal", "median": 256, "sigma": 0.6,
                           "min": 64, "max": 1024}
    prompts = traffic.length_set(t["prompt"], 64)
    assert int(prompts.sum()) == 205_439 and int((prompts > 8192).sum()) == 5
    assert int((prompts == 16384).sum()) == 1
    cell = harness.load_cell(CELL)
    e = cell["engine"]
    assert e["capacity"] == 16384 + 1024 and e["page_size"] == 64
    assert 4096 <= e["num_pages"] <= 8192 and not e.get("prefill_chunk")
    assert {m["name"] for m in cell["end_to_end"]} == {
        "setup_s", "serve_tokens_per_s", "ttft_p50_ms", "itl_p95_ms"}
    assert len(cell["per_layer"]) == 15


def test_flops_and_bytes_by_hand(sizes):
    e = sizes.d_model
    fixed = kc.fixed_matmul_params(sizes)
    attn = e * 1536 + 1536 * 12288 + e * 576 + 512 * 16384 + 8192 * e
    assert fixed == 5 * attn + 3 * e * 18432 + 4 * (e * 384 + 3 * e * 2048) \
        + e * 20480
    # one decoded token at context 1000, absorbed: 64 heads of 576 + 512
    one = kc.serve_flops(sizes, [], [1000], assigned=3)
    assert one == 2 * fixed + 2 * 3 * 44_040_192 \
        + 5 * 2 * 64 * 1088 * 1000
    # a prompt of 10, published form: 55 causal pairs of 64 heads of 320
    assert kc.serve_flops(sizes, [10], []) == pytest.approx(
        2 * 10 * fixed + 2 * 10 * 44_040_192 + 5 * 2 * 64 * 320 * 55)
    weights = 2 * (kc.num_params(sizes) - 20480 * e - 4 * 12 * 44_040_192)
    assert kc.decode_tick_bytes(sizes, 1050, 14) == weights \
        + 14 * 88_080_384 + 1050 * 5_760
    # 2.56 GFLOP a token before attention: the 2.3 the issue prices the
    # traffic with, and 0.29 for the head
    a_token = 2 * fixed + 2 * kc.expected_assignments(sizes, 1) * 44_040_192
    assert 2.2e9 < a_token - 2 * e * 20480 < 2.4e9


def ev(name, start_ms, dur_ms, **attrs):
    return [name, int(start_ms * MS), int(dur_ms * MS), attrs]


def sample():
    """Two decode ticks and a prefill in a window of 100 ms; the decode
    programs run 20 and 25 ms on the device."""
    host = [ev("perf_window", 0, 100),
            ev("serve.tick", 2, 30),
            ev("serve.decode", 4, 26, pages=16, moe_assigned=5,
               moe_touched=4, latent_rows=1050),
            ev("serve.tick", 40, 55),
            ev("serve.prefill", 41, 20, req=7, n=300, bucket=512,
               moe_assigned=290),
            ev("serve.decode", 62, 30, pages=16, moe_assigned=9,
               moe_touched=6, latent_rows=1353)]
    modules = [["jit_run_decode_p16(3)", 5 * MS, 20 * MS],
               ["jit_run_prefill_b512(4)", 42 * MS, 15 * MS],
               ["jit_run_decode_p16(3)", 63 * MS, 25 * MS]]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops",
             "events": [["fusion.1", m[1], m[2]] for m in modules] + [
                 [f"latent_decode_attention.{i}", (start + i) * MS, MS // 100]
                 for start in (6, 64) for i in range(5)]}]},
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
    assert mr.moe_tokens_per_expert(ctx, {}) == (5 / 4 + 9 / 6) / 2
    assert kr.latent_rows_per_slot(ctx, {}) == (1050 / 2 + 1353 / 3) / 2
    shares = [kc.decode_tick_bytes(sizes, rows, touched) / 819e9 / s
              for rows, touched, s in ((1050, 4, 0.020), (1353, 6, 0.025))]
    assert kr.decode_hbm_roofline(ctx, {}) == pytest.approx(
        100 * sum(shares) / 2)
    flops = kc.serve_flops(sizes, [300], [1000, 50, 1001, 51, 301],
                           5 + 290 + 9)
    assert kr.serve_mfu(ctx, {}) == pytest.approx(
        100 * flops / 0.100 / 197e12)
    assert 0 < kr.decode_hbm_roofline(ctx, {}) < 100
    assert 0 < kr.serve_mfu(ctx, {}) < 100
    # 10 kernel events of 10 us; 2,403 rows x 5 layers x 1,152 B at 819 GB/s
    args = harness.load_json("metrics", "latent_attn_roofline")["args"]
    assert kr.latent_attn_roofline(ctx, args) == pytest.approx(
        100 * 2403 * 5760 / 819e9 / 1e-4)
    assert ctx["notes"]["latent_attn_bound"] == "bytes"
    assert 0 < kr.latent_attn_roofline(ctx, args) < 100
    assert kr.latent_attn_roofline(ctx, {"pattern": "^no_such"}) is None


def test_readers_find_nothing_without_the_counters(sizes):
    """The parent's spans (no ``latent_rows``; here no routing counters
    either): every reader answers ``None`` or falls back, none raises."""
    trace, calls = sample()
    for e in trace["planes"][1]["lines"][0]["events"]:
        e[3].pop("latent_rows", None)
    ctx = context(sizes, trace, calls)
    assert kr.latent_rows_per_slot(ctx, {}) is None
    assert kr.decode_hbm_roofline(ctx, {}) is None
    assert kr.latent_attn_roofline(
        ctx, {"pattern": "^latent_decode_attention"}) is None
    assert mr.moe_tokens_per_expert(ctx, {}) is not None
    for e in trace["planes"][1]["lines"][0]["events"]:
        for key in ("moe_assigned", "moe_touched"):
            e[3].pop(key, None)
    ctx = context(sizes, trace, calls)
    assert mr.moe_tokens_per_expert(ctx, {}) is None
    assert kr.decode_hbm_roofline(ctx, {}) is None
    flops = kc.serve_flops(sizes, [300], [1000, 50, 1001, 51, 301])
    assert kr.serve_mfu(ctx, {}) == pytest.approx(
        100 * flops / 0.100 / 197e12)


@pytest.fixture(scope="module")
def rehearsed():
    return k2_rehearsal.run_cell(CELL, SEED, 1.5, False)


def test_cell_rehearses_correct(rehearsed):
    assert rehearsed["correct"], rehearsed["checked"]
    assert set(rehearsed["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                         "ttft_p50_ms", "itl_p95_ms"}
    assert rehearsed["failed"] == 0 and rehearsed["attempted"] > 4
    assert rehearsed["checked"]["compiles_in_window"]["value"] == 0


def test_traced_rehearsal_reads_the_counters():
    res = k2_rehearsal.run_cell(CELL, SEED + 1, 1.5, True)
    assert res["correct"], res["checked"]
    # no device plane on the CPU: the span readers answer, the device
    # readers find nothing and are left out, none raises
    assert 1.0 <= res["metrics"]["moe_tokens_per_expert.k2"]["value"] <= 4
    assert 2 <= res["metrics"]["latent_rows_per_slot"]["value"] <= 1088
    assert "decode_hbm_roofline.k2" not in res["metrics"]


def test_control_fails_the_rehearsal_limits():
    """The fp8 reference's own first choices, put in the served tokens'
    place, come out of the cell's comparison as not correct: the
    rehearsal holds the two numbers the committed cell holds."""
    from perf import compare, serve_k2_runner as runner

    cell, tiny = k2_rehearsal.shrink(harness.load_cell(CELL))
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
    """``perf/k2_limits.py`` puts the program's and the control's numbers
    through ``compare.checked_from`` with the cell's limits: the program
    correct, the control not, on every seed."""
    from perf import k2_limits

    rc = k2_limits.main(["--workload", CELL, "--seeds", "2",
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


def test_counters_equal_the_references_count_and_the_recorders():
    """``decode_hbm_roofline.k2`` prices a tick by the program's own
    ``moe_touched`` and ``latent_rows``: the routing counters equal what
    the reference's router picks for the same tokens, and ``latent_rows``
    is the rows the tick's queries attend, counted here."""
    import jax.numpy as jnp
    from ddl_tpu.serve import ServeConfig, engine_cls
    from perf import k2_reference as ref, serve_k2_runner as runner

    import dataclasses

    tiny = dataclasses.replace(k2_rehearsal.TINY, experts_held=(4, 12))
    weights = kw.make_weights(SEED, tiny, "float32")
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
        assert eng.last_counters == {"moe_assigned": held(choices(seq))[0]}
        seq.append(tok)
    lengths = np.asarray([len(s) - 1 for s in seqs], np.int32)
    active = np.asarray([True, True, False])
    eng.decode(np.asarray([s[-1] for s in seqs], np.int32), lengths,
               np.arange(3, dtype=np.int32), active)
    tick = np.stack([choices(s)[:, -1] for s in seqs[:2]], axis=1)
    assigned, touched = held(tick)
    assert 0 < touched <= assigned <= 2 * tiny.top_k
    assert eng.last_counters == {"moe_assigned": assigned,
                                 "moe_touched": touched,
                                 "latent_rows": 6 + 20}
