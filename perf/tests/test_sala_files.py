"""The two-mixer block's benchmark files: the counts against the tree and
the issue's table, the configuration against the catalog, the traffic's
quantiles, the new readers on a trace small enough to work by hand, the
cell's runner rehearsed at toy sizes, and the control."""

import json
import os

import numpy as np
import pytest

from perf import (harness, sala_counts as sc, sala_readers as sr,
                  sala_rehearsal, sala_weights as sw)

CONFIG, CELL = "minicpm-sala-l8", "serve-sala-closed64-32k"
SEED = 2_345_678_901  # above 2**31, as the driver's are
MS = 1_000_000


@pytest.fixture(scope="module")
def sizes():
    return sw.load_sizes(CONFIG)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(harness.HERE, "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


def test_counts_equal_the_tree_the_file_and_the_table(sizes, cfg):
    import jax

    from perf import sala_reference as ref

    tree = jax.eval_shape(lambda: sw.make_weights(1, sizes))
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert leaves == sc.num_params(sizes) == cfg["parameters"] \
        == 2_820_569_088
    assert all(a.dtype == "bfloat16" for a in jax.tree.leaves(tree))
    from ddl_tpu.models import hybrid
    from perf.serve_sala_runner import spec_of

    assert hybrid.NAMED_SPECS[CONFIG] == spec_of(sizes)
    assert hybrid.NAMED_SPECS[CONFIG].num_params == leaves
    # the issue's table, by part
    layer = lambda i: sum(int(np.prod(s))
                          for s in sw.block_shapes(sizes, i).values())
    assert layer(0) == layer(7) == 253_763_840          # minicpm4
    assert {layer(i) for i in range(1, 7)} == {285_225_216}  # lightning-attn
    assert 2 * 73448 * 4096 + 4096 == 601_690_112
    assert sc.state_bytes(sizes) == 12 * 2 ** 20          # 12 MiB a slot
    # K and V of 2 K/V heads in 2 sparse layers: 2,048 B a token
    assert sc.layers_of(sizes, sw.SPARSE) == 2
    assert 2 * sizes.kv_heads * sc.page_bytes(sizes) // 64 == 2_048
    # the same seed gives the program and the reference the same leaves
    tiny = sala_rehearsal.TINY
    a, b = (ref.leaf_norms(sw.make_weights(SEED, tiny, "float32"))
            for _ in range(2))
    assert a == b and a["blocks"][1]["on"] == pytest.approx(32 ** 0.5)
    assert a != ref.leaf_norms(sw.make_weights(SEED + 1, tiny, "float32"))


def test_config_file_states_the_cut(sizes, cfg):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "MiniCPM-SALA")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            want = cfg["published"][key] if key in cfg["reduced"] else cfg[key]
            assert want == value, key
        assert cfg["mixer_types"] == row["config"]["mixer_types"][9:17]
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert cfg["num_hidden_layers"] == 8
    assert cfg["mixer_types"] == ["minicpm4"] + ["lightning-attn"] * 6 \
        + ["minicpm4"]
    assert cfg["published"]["num_hidden_layers"] == 32
    assert cfg["published"]["mixer_types"].count("minicpm4") == 8
    assert cfg["deployment"]["chips_sharing_a_layer"] == 1
    assert set(cfg["assumed"]) >= {
        "sparse_config", "selection", "selector_cache", "lightning_decay",
        "lightning_activation", "qk_norm", "rotary_pairing", "output_norm",
        "output_gate", "scalings", "weights", "context"}
    assert cfg["assumed"]["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "init_blocks": 1, "window_size": 2048, "dense_len": 8192}
    assert (sizes.d_model, sizes.num_heads, sizes.head_dim, sizes.kv_heads,
            sizes.d_ff, sizes.vocab, sizes.depth) == (
        4096, 32, 128, 2, 16384, 73448, 32)
    assert sizes.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert sizes.logit_scale == 1 / 16 and sizes.scale_emb == 12
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_traffic_is_the_issues(sizes):
    from perf import traffic

    t = harness.load_json("traffic", "closed64-32k")
    assert (t["kind"], t["clients"], t["block"], t["order_seed"]) == (
        "closed_loop", 64, 64, 0)
    assert t["prompt"] == {"dist": "lognormal", "median": 8192, "sigma": 0.8,
                           "min": 1024, "max": 32768}
    assert t["output"] == {"dist": "lognormal", "median": 1024, "sigma": 0.6,
                           "min": 256, "max": 3072}
    prompts = traffic.length_set(t["prompt"], 64)
    outputs = traffic.length_set(t["output"], 64)
    assert int(prompts.sum()) == 682_651 and int(prompts.max()) == 32768
    assert int((prompts > sizes.dense_len).sum()) == 32     # half past it
    assert int(outputs.sum()) == 76_507 and int(outputs.max()) == 3072
    cell = harness.load_cell(CELL)
    e = cell["engine"]
    assert e["capacity"] == 32768 + 3072 and e["page_size"] == 64
    assert e["page_size"] == sizes.block_size
    # every block of 64 requests resident at once, as paired and ordered
    order = np.random.default_rng(t["order_seed"])
    paired = order.permutation(prompts) + order.permutation(outputs)
    assert int((-(-paired // 64)).sum()) <= e["num_pages"]
    assert e["prefill_chunk"] % sizes.kernel_stride == 0
    assert {m["name"] for m in cell["end_to_end"]} == {
        "setup_s", "serve_tokens_per_s", "ttft_p50_ms", "itl_p95_ms"}
    assert len(cell["per_layer"]) == 14
    assert {m["name"] for m in cell["per_layer"]} >= {
        "serve_mfu_pct.sala", "decode_hbm_roofline.sala",
        "sparse_attn_roofline", "sparse_pages_read_pct"}


def test_flops_and_bytes_by_hand(sizes):
    e = sizes.d_model
    fixed = sc.fixed_matmul_params(sizes)
    assert fixed == 2 * (3 * e * e + 2 * e * 256) + 6 * 5 * e * e \
        + 8 * 3 * e * 16384
    head = 2 * e * 73448
    # one decoded token at context 1000 (every row) and at 12,000 (64
    # blocks: 63 whole and 32 rows of its own, 749 compressed keys)
    assert sc.attended(sizes, 999) == (1000, 0)
    assert sc.attended(sizes, 11_999) == (63 * 64 + 32, 749)
    assert sc.serve_flops(sizes, [], [1000]) == 2 * fixed + head \
        + 6 * 4 * 32 * 128 * 128 + 2 * 4 * 32 * 128 * 1000
    assert sc.serve_flops(sizes, [], [12_000]) == 2 * fixed + head \
        + 6 * 4 * 32 * 128 * 128 \
        + 2 * (4 * 32 * 128 * 4064 + 2 * 32 * 128 * 749)
    # a prefill block of 10 from 0: 55 causal pairs, one head row
    assert sc.serve_flops(sizes, [(0, 10, False)], []) == pytest.approx(
        10 * (2 * fixed + 6 * 4 * 32 * 128 * 128) + head
        + 2 * 4 * 32 * 128 * 55)
    # a block past dense_len that ran dense (a CPU of another program)
    # counts every pair, one that ran the selector the chosen rows
    dense = sc.sparse_flops(sizes, 8192, 64, False)
    assert dense == 4 * 32 * 128 * (64 * 8192 + 64 * 65 // 2)
    assert sc.sparse_flops(sizes, 8192, 64) < dense / 1.9
    # 5.0 GFLOP a token before attention, 0.6 of it the head's on the
    # tokens that need logits
    assert 4.3e9 < 2 * fixed < 4.5e9 and 0.59e9 < head < 0.61e9
    weights = 2 * (sc.num_params(sizes) - 73448 * e)
    contexts = [12_000, 9_000, 500]
    assert sc.selector_bytes(sizes, contexts) == (750 + 562) * 2 * 128 * 2
    assert sc.decode_tick_bytes(sizes, 300, 3, contexts) == weights \
        + 3 * 2 * 12 * 2 ** 20 + 2 * 300 * 32_768 \
        + 2 * sc.selector_bytes(sizes, contexts)


def ev(name, start_ms, dur_ms, **attrs):
    return [name, int(start_ms * MS), int(dur_ms * MS), attrs]


def sample():
    """Two decode ticks and a prefill chunk in a window of 100 ms; the
    decode programs run 20 and 25 ms on the device."""
    host = [ev("perf_window", 0, 100),
            ev("serve.tick", 2, 30),
            ev("serve.decode", 4, 26, pages=256, kv_pages=2 * (188 + 8),
               sparse_pages=2 * (64 + 8), state_slots=2),
            ev("serve.tick", 40, 55),
            ev("serve.prefill", 41, 20, req=7, n=300, bucket=512, chunk=2,
               sparse=1),
            ev("serve.decode", 62, 30, pages=256,
               kv_pages=2 * (188 + 8 + 137), sparse_pages=2 * (64 + 8 + 64),
               state_slots=3)]
    modules = [["jit_run_decode_p256(3)", 5 * MS, 20 * MS],
               ["jit_run_prefill_b512(4)", 42 * MS, 15 * MS],
               ["jit_run_decode_p256(3)", 63 * MS, 25 * MS]]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops",
             "events": [["fusion.1", m[1], m[2]] for m in modules] + [
                 [f"sparse_decode_attention.{i}", (start + i) * MS, MS // 10]
                 for start in (6, 64) for i in range(2)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]}]}
    calls = [{"kind": "decode", "contexts": [12_000, 500],
              "resident_tokens": 12_500},
             {"kind": "prefill", "tokens": 300, "req": 7},
             {"kind": "decode", "contexts": [12_001, 501, 8_750],
              "resident_tokens": 21_252}]
    return trace, calls


def context(sizes, trace, calls):
    cell = harness.load_cell(CELL)
    return {"span_trace": trace, "trace": trace, "cell": cell, "sizes": sizes,
            "facts": {"traced_calls": calls},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def test_readers_on_a_sample_by_hand(sizes):
    trace, calls = sample()
    ctx = context(sizes, trace, calls)
    chunk = ctx["cell"]["engine"]["prefill_chunk"]
    assert sr.sparse_pages_read_pct(ctx, {}) == pytest.approx(
        100 * (72 + 136) / (196 + 333))
    shares = [sc.decode_tick_bytes(sizes, pages, slots, c) / 819e9 / s
              for pages, slots, c, s in (
                  (144, 2, [12_000, 500], 0.020),
                  (272, 3, [12_001, 501, 8_750], 0.025))]
    assert sr.decode_hbm_roofline(ctx, {}) == pytest.approx(
        100 * sum(shares) / 2)
    flops = sc.serve_flops(sizes, [(2 * chunk, 300, True)],
                           [12_000, 500, 12_001, 501, 8_750])
    assert sr.serve_mfu(ctx, {}) == pytest.approx(
        100 * flops / 0.100 / 197e12)
    assert ctx["notes"]["sparse_prefill_blocks"] == [1, 1]
    assert 0 < sr.decode_hbm_roofline(ctx, {}) < 100
    assert 0 < sr.serve_mfu(ctx, {}) < 100
    # 4 kernel events of 100 us; (144 + 272) pages x 2 layers x 32,768 B
    args = harness.load_json("metrics", "sparse_attn_roofline")["args"]
    assert sr.sparse_attn_roofline(ctx, args) == pytest.approx(
        100 * 416 * 2 * 32_768 / 819e9 / 4e-4)
    assert 0 < sr.sparse_attn_roofline(ctx, args) < 100
    assert sr.sparse_attn_roofline(ctx, {"pattern": "^no_such"}) is None


def test_readers_find_nothing_without_the_counters(sizes):
    """The parent's spans (none of the new counters): every reader
    answers ``None``, none raises."""
    trace, calls = sample()
    for e in trace["planes"][1]["lines"][0]["events"]:
        for key in ("kv_pages", "sparse_pages", "state_slots", "chunk",
                    "sparse"):
            e[3].pop(key, None)
    ctx = context(sizes, trace, calls)
    assert sr.sparse_pages_read_pct(ctx, {}) is None
    assert sr.decode_hbm_roofline(ctx, {}) is None
    assert sr.serve_mfu(ctx, {}) is None
    assert sr.sparse_attn_roofline(
        ctx, {"pattern": "^sparse_decode_attention"}) is None


@pytest.fixture(scope="module")
def rehearsed():
    return sala_rehearsal.run_cell(CELL, SEED, 1.5, False)


def test_cell_rehearses_correct(rehearsed):
    assert rehearsed["correct"], rehearsed["checked"]
    assert set(rehearsed["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                         "ttft_p50_ms", "itl_p95_ms"}
    assert rehearsed["failed"] == 0 and rehearsed["attempted"] > 4
    assert rehearsed["checked"]["compiles_in_window"]["value"] == 0


def test_traced_rehearsal_reads_the_counters():
    res = sala_rehearsal.run_cell(CELL, SEED + 1, 1.5, True)
    assert res["correct"], res["checked"]
    # no device plane on the CPU: the span readers answer, the device
    # readers find nothing and are left out, none raises
    assert 5 < res["metrics"]["sparse_pages_read_pct"]["value"] < 100
    for name in ("decode_hbm_roofline.sala", "sparse_attn_roofline",
                 "serve_mfu_pct.sala"):
        assert name not in res["metrics"]


def test_control_fails_the_rehearsal_limits():
    """The fp8 reference's own first choices, put in the served tokens'
    place, come out of the cell's comparison as not correct: the
    rehearsal holds the two numbers the committed cell holds."""
    from perf import compare, serve_sala_runner as runner

    cell, tiny = sala_rehearsal.shrink(harness.load_cell(CELL))
    limits = cell["check"]["limits"]
    assert list(limits) == list(harness.load_cell(CELL)["check"]["limits"])
    rng = np.random.default_rng(5)
    served = [(rng.integers(0, tiny.vocab, n).astype(np.int32),
               rng.integers(0, tiny.vocab, 24).astype(np.int32))
              for n in (40, 200, 300)]
    got = runner.reference_gaps(cell, tiny, SEED, served, control=True)
    checked = compare.checked_from(
        dict(got["control"], requests_failed=0, compiles_in_window=0), limits)
    assert not harness.judge(checked), checked
    assert all(e["value"] <= e["limit"] for k, e in checked.items()
               if not k.startswith("logit_gap"))


def test_limits_tool_judges_program_and_control(capsys):
    """``perf/sala_limits.py`` puts the program's and the control's
    numbers through ``compare.checked_from`` with the cell's limits: the
    program correct, the control not, on every seed."""
    from perf import sala_limits

    rc = sala_limits.main(["--workload", CELL, "--seeds", "2",
                           "--control-seeds", "2", "--seconds", "1.5",
                           "--first-seed", str(SEED), "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["separated"]
    assert set(line["limits"]) >= {"logit_gap_mean", "logit_gap_p99"}
    assert all(r["correct"] and not r["over"]
               for r in line["readings"]["program"].values())
    control = line["readings"]["control_fp8"]
    assert len(control) == 2
    assert all(not r["correct"] and r["over"] for r in control.values())


def test_counters_equal_a_count_by_hand():
    """``decode_hbm_roofline.sala`` prices a tick by the program's own
    ``sparse_pages`` and ``state_slots``: they are what the tick's
    lengths say, counted here, and a prefill's ``chunk`` is its block's
    index in the prompt."""
    from ddl_tpu.serve import ServeConfig, engine_cls
    from perf import serve_sala_runner as runner

    tiny = sala_rehearsal.TINY          # pages of 8, top 8, dense_len 128
    weights = sw.make_weights(SEED, tiny, "float32")
    spec = runner.spec_of(tiny)
    eng = engine_cls(spec)(ServeConfig(
        spec=spec, slots=3, capacity=320, page_size=8, prefill_chunk=64),
        params=weights)
    rng = np.random.default_rng(2)
    seqs = [list(rng.integers(0, tiny.vocab, n)) for n in (5, 200, 127)]
    for slot, seq in enumerate(seqs):
        for base in range(0, len(seq), 64):
            tok, _ = eng.prefill(np.asarray(seq[base:base + 64], np.int32),
                                 slot=slot, request_id=slot, base=base)
            assert eng.last_counters == {
                "chunk": base // 64,
                "sparse": int(min(base + 64, len(seq)) > 128)}
        seq.append(tok)
    lengths = np.asarray([len(s) - 1 for s in seqs], np.int32)
    eng.decode(np.asarray([s[-1] for s in seqs], np.int32), lengths,
               np.arange(3, dtype=np.int32), np.asarray([True, True, True]))
    # contexts 6, 201 (past 128: the top 8 of 26 pages), 128 (all 16)
    assert eng.last_counters == {"state_slots": 3,
                                 "kv_pages": 2 * (1 + 26 + 16),
                                 "sparse_pages": 2 * (1 + 8 + 16)}
