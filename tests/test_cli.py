"""CLI layer (the run.sh replacement, SURVEY.md §1 launcher layer):
argument → config mapping, plus end-to-end drives of ``main()`` — every
variant trains a tiny run to completion through the real entry point on
the virtual 8-device mesh."""

import json
import subprocess
import sys

import numpy as np
import pytest

from ddl_tpu.cli import build_parser, config_from_args, main


def _cfg(argv):
    return config_from_args(build_parser().parse_args(argv))


def test_sharding_variant_maps_num_ps():
    # reference: run.sh $1=num_ps $2=num_workers (mnist_sync_sharding/run.sh)
    cfg = _cfg(["sync_sharding", "--num-ps", "4", "--num-workers", "8"])
    assert cfg.num_ps == 4
    assert cfg.num_workers == 8
    assert cfg.layout == "block"


def test_greedy_variant_defaults_zigzag():
    cfg = _cfg(["async_sharding_greedy", "--num-ps", "2", "--num-workers", "4"])
    assert cfg.layout == "zigzag"
    assert cfg.num_ps == 2


def test_unsharded_variant_forces_single_ps():
    cfg = _cfg(["sync", "--num-ps", "5", "--num-workers", "4"])
    assert cfg.num_ps == 1  # unsharded variants ignore --num-ps


def test_reference_compat_flags():
    cfg = _cfg(["sync", "--num-workers", "2", "--reference-compat"])
    assert cfg.grad_reduction == "sum"
    assert cfg.shard_data is False
    default = _cfg(["sync", "--num-workers", "2"])
    assert default.grad_reduction == "mean"
    assert default.shard_data is True


def test_reference_hyperparameter_defaults():
    # epoch=1, batch=100, lr=1e-4, keep_prob=0.5, eval every 10
    # (reference worker.py:41-42, model.py:93, worker.py:30,71).
    cfg = _cfg(["single"])
    assert cfg.epochs == 1
    assert cfg.batch_size == 100
    assert cfg.learning_rate == 1e-4
    assert cfg.keep_prob == 0.5
    assert cfg.eval_every == 10
    assert cfg.num_workers == 1


def test_device_shortfall_is_an_error_never_a_cpu_swap(monkeypatch, capsys):
    """A run that asks for more devices than the active platform has
    exits non-zero naming both counts, on every variant — the virtual
    CPU mesh is chosen by --platform cpu only, never swapped in."""
    import jax

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    small = ["--tiny", "--synthetic-train", "64", "--synthetic-test", "64"]
    for argv in (
        ["sync", "--num-workers", "4"] + small,
        ["lm", "--num-workers", "2", "--data-parallel", "2"],
        ["serve", "--tensor-parallel", "2"],
    ):
        with pytest.raises(SystemExit) as exc:
            main(argv)
        msg = str(exc.value)
        assert exc.value.code not in (0, None), argv
        assert "needs" in msg and "has 1" in msg, msg
        assert str(4 if argv[0] != "serve" else 2) in msg, msg
    out = capsys.readouterr().out
    assert "CPU mesh" not in out and "falling back" not in out


def test_bf16_flag():
    assert _cfg(["single", "--bf16"]).compute_dtype == "bfloat16"
    # Off-TPU (this CPU test host) the auto default is fp32; on a TPU
    # platform it would be bf16 (--fp32 to override) — cli._resolve_dtype.
    assert _cfg(["single"]).compute_dtype is None
    assert _cfg(["single", "--fp32"]).compute_dtype is None
    import pytest

    with pytest.raises(SystemExit, match="mutually exclusive"):
        _cfg(["single", "--bf16", "--fp32"])


def test_default_batch_rounds_to_worker_multiple():
    # ADVICE r1: `sync --num-workers 8` must not crash on 100 % 8 != 0.
    cfg = _cfg(["sync", "--num-workers", "8"])
    assert cfg.batch_size == 104
    assert cfg.per_worker_batch() == 13
    # Explicit divisible batch is honored verbatim.
    assert _cfg(["sync", "--num-workers", "8", "--batch-size", "200"]).batch_size == 200
    # Compat stream replicates data — the reference batch stays exactly 100.
    assert _cfg(["sync", "--num-workers", "8", "--reference-compat"]).batch_size == 100


def test_explicit_indivisible_batch_fails_fast():
    with pytest.raises(SystemExit, match="not divisible"):
        _cfg(["sync", "--num-workers", "8", "--batch-size", "100"])


# ---------------------------------------------------------------------------
# End-to-end: main() trains every variant on the 8-device mesh (VERDICT r2
# task 7). --tiny narrow model + small procedural data keep each run to a
# few seconds; the JSON line is the machine-readable contract.

_E2E = [
    "--tiny", "--batch-size", "16", "--synthetic-train", "512",
    "--synthetic-test", "64", "--eval-every", "4", "--json",
]


def _run_main(argv, capsys, *, expect_steps=True):
    assert main(argv) == 0
    out = capsys.readouterr().out
    payload = json.loads(out.strip().splitlines()[-1])
    assert 0.0 <= payload["final_accuracy"] <= 1.0
    if expect_steps:
        assert payload["step_stats"]["steps"] > 0
        assert payload["images_per_sec"] > 0
    return payload


@pytest.mark.parametrize("variant", [
    "single", "sync", "async", "sync_sharding", "async_sharding",
    "sync_sharding_greedy", "async_sharding_greedy",
])
def test_main_end_to_end(variant, capsys):
    argv = [variant] + _E2E
    if variant != "single":
        argv += ["--num-workers", "8"]
    if "sharding" in variant:
        argv += ["--num-ps", "4"]
    payload = _run_main(argv, capsys)
    assert payload["variant"] == variant
    assert payload["config"]["conv_channels"] == [4, 8, 8, 8]


def test_main_lm_end_to_end(capsys):
    """The lm variant (sequence-parallel decoder LM, strategies/seq.py)
    trains end-to-end through main() on the 8-device mesh: ring attention
    over the copy task, JSON contract with tokens_per_sec."""
    payload = _run_main([
        "lm", "--num-workers", "8", "--seq-len", "32", "--vocab", "16",
        "--d-model", "32", "--heads", "2", "--layers", "2", "--d-ff", "64",
        "--train-seqs", "64", "--test-seqs", "16", "--batch-size", "16",
        "--eval-every", "2", "--json",
    ], capsys, expect_steps=False)
    assert payload["variant"] == "lm"
    assert payload["config"]["scheme"] == "ring"
    assert payload["tokens_per_sec"] > 0
    assert np.isfinite(payload["final_loss"])


def test_main_lm_rejects_mnist_only_flags(capsys):
    with pytest.raises(SystemExit, match="--tiny"):
        main(["lm", "--tiny"])
    with pytest.raises(SystemExit, match="--fused-adam"):
        main(["lm", "--fused-adam"])


def test_main_reference_compat_end_to_end(capsys):
    payload = _run_main(
        ["sync", "--num-workers", "8", "--reference-compat"] + _E2E, capsys
    )
    assert payload["config"]["grad_reduction"] == "sum"
    assert payload["config"]["shard_data"] is False


def test_main_conv1_matmul_end_to_end(capsys):
    """--conv1-matmul (patches-matmul first conv) trains end-to-end through
    the DP collective path; model-level numerics parity is pinned by
    tests/test_model.py::test_first_conv_matmul_matches_conv."""
    payload = _run_main(
        ["sync", "--num-workers", "8", "--conv1-matmul"] + _E2E, capsys
    )
    assert payload["config"]["conv1_matmul"] is True


def test_main_checkpoint_resume_roundtrip(tmp_path, capsys):
    d = str(tmp_path / "ckpt")
    args = ["sync_sharding", "--num-workers", "8", "--num-ps", "8",
            "--layout", "flat", "--checkpoint-dir", d] + _E2E
    _run_main(args, capsys)
    # All 32 batches were done by run 1, so the resumed run replays nothing
    # (zero spans dispatched — expect_steps off).
    resumed = _run_main(args + ["--resume"], capsys, expect_steps=False)
    assert resumed["resumed_from_step"] == 32


def test_cli_subprocess_smoke():
    """The real process path: python -m ddl_tpu with an explicit --platform
    cpu (the virtual mesh, sized before the backend exists) in a fresh
    interpreter."""
    proc = subprocess.run(
        [sys.executable, "-m", "ddl_tpu", "sync_sharding_greedy",
         "--platform", "cpu", "--num-workers", "8", "--num-ps", "4"] + _E2E,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    assert payload["variant"] == "sync_sharding_greedy"
    assert payload["config"]["layout"] == "zigzag"


def test_cli_sigterm_checkpoints_and_resumes(tmp_path):
    """The real preemption path: SIGTERM to a running `python -m ddl_tpu`
    makes it checkpoint, report preempted=true, and exit 0; a --resume
    invocation finishes the job."""
    import os
    import signal as sig

    d = str(tmp_path / "ck")
    args = [sys.executable, "-m", "ddl_tpu", "single", "--platform", "cpu",
            "--tiny", "--synthetic-train", "512", "--synthetic-test", "64",
            "--batch-size", "64", "--eval-every", "2", "--epochs", "200",
            "--checkpoint-dir", d, "--json"]
    env = dict(os.environ, PYTHONUNBUFFERED="1")
    proc = subprocess.Popen(args, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=env)
    try:
        # Wait for training to actually progress, then deliver SIGTERM.
        for line in proc.stdout:
            if line.startswith("epoch:"):
                proc.send_signal(sig.SIGTERM)
                break
        out, err = proc.communicate(timeout=240)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, err[-2000:]
    payload = json.loads(out.strip().splitlines()[-1])
    assert payload["preempted"] is True
    assert os.path.exists(os.path.join(d, "ckpt.npz"))

    resumed = subprocess.run(
        args[:-1] + ["--resume", "--epochs", "1", "--json"],
        capture_output=True, text=True, timeout=240,
    )
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    rp = json.loads(resumed.stdout.strip().splitlines()[-1])
    assert rp["preempted"] is False
    assert rp["resumed_from_step"] > 0


def test_main_serve_prefix_cache_and_chunked_prefill(capsys):
    """The serve variant end-to-end through main() with the ISSUE 4
    flags: a prefix-cache pool plus chunked prefill under a tick
    budget, JSON contract carrying the new SLO fields (ttft/itl) and
    the prefix ledger. Tiny model + 4 tokens/request keeps this inside
    the tier-1 budget."""
    assert main([
        "serve", "--slots", "2", "--capacity", "64", "--max-new-tokens",
        "4", "--num-prompts", "3", "--prompt-min", "6", "--prompt-max",
        "12", "--vocab", "16", "--d-model", "32", "--heads", "2",
        "--layers", "2", "--d-ff", "64", "--prefix-cache", "2",
        "--prefill-chunk", "8", "--prefill-budget", "8", "--json",
    ]) == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload["variant"] == "serve"
    assert payload["config"]["prefix_slots"] == 2
    assert payload["config"]["prefill_chunk"] == 8
    assert payload["prefix_lookups"] == 3
    assert payload["ttft_ms"]["p95"] > 0
    assert len(payload["completions"]) == 3
    # ISSUE 8 satellite: the single-engine path tallies one "default"
    # class — same JSON shape the router path fills with real classes.
    assert payload["per_class"] == {
        "default": {"total": 3, "ok": 3, "shed": 0,
                    "deadline_exceeded": 0}
    }
    assert all(len(c["tokens"]) == 4
               for c in payload["completions"].values())


def test_main_serve_paged_pool_end_to_end(capsys):
    """ISSUE 7 CLI surface: ``--page-size``/``--num-pages`` serve the
    same workload on the paged pool (prefix sharing + chunking on — the
    full composition), with the JSON contract carrying the page fields
    and the warmup having compiled the page-count ladders (any jit
    inside the run would still pass, but the run exercises the paged
    warmup path end to end)."""
    assert main([
        "serve", "--slots", "2", "--capacity", "64", "--max-new-tokens",
        "4", "--num-prompts", "3", "--prompt-min", "6", "--prompt-max",
        "12", "--vocab", "16", "--d-model", "32", "--heads", "2",
        "--layers", "2", "--d-ff", "64", "--prefix-cache", "2",
        "--prefill-chunk", "8", "--page-size", "8", "--num-pages", "12",
        "--json",
    ]) == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload["variant"] == "serve"
    assert payload["config"]["page_size"] == 8
    assert payload["config"]["num_pages"] == 12
    assert payload["kv_pages_free"] >= 0
    assert len(payload["completions"]) == 3
    assert all(c["status"] == "ok" and len(c["tokens"]) == 4
               for c in payload["completions"].values())


def test_main_serve_slo_rules_and_prom_port(capsys):
    """ISSUE 10 CLI surface: ``--slo-rules`` arms the streaming
    burn-rate monitor on the single-engine serve path (every TTFT
    misses the 1ns target, so the rule alerts) and ``--prom-port 0``
    stands up the /metrics endpoint for the run (ephemeral port,
    printed). The JSON contract carries the per-rule burn/alert
    digest; flag hygiene rejects the flag off the serve variant."""
    assert main([
        "serve", "--slots", "2", "--capacity", "64", "--max-new-tokens",
        "4", "--num-prompts", "3", "--prompt-min", "6", "--prompt-max",
        "12", "--vocab", "16", "--d-model", "32", "--heads", "2",
        "--layers", "2", "--d-ff", "64", "--prom-port", "0",
        "--slo-rules",
        "ttft:metric=serve_ttft_seconds,target=0.000000001,fast=2,slow=4,"
        "objective=0.5",
        "--json",
    ]) == 0
    out = capsys.readouterr().out
    assert "metrics endpoint: http://127.0.0.1:" in out
    payload = json.loads(out.strip().splitlines()[-1])
    row = payload["slo_rules"]["ttft"]
    assert row["alerts"] >= 1 and row["fired_ticks"]
    assert row["slow_burn"] > 1.0
    with pytest.raises(SystemExit, match="--slo-rules does not apply"):
        main(["lm", "--platform", "cpu", "--slo-rules",
              "r:metric=m,target=1"])
    with pytest.raises(SystemExit, match="--slo-rules"):
        main(["serve", "--platform", "cpu", "--slo-rules", "bogus"])


def test_main_serve_router_end_to_end_from_checkpoint(tmp_path, capsys):
    """ISSUE 8 CLI surface: a tiny lm training run leaves a checkpoint;
    ``serve --replicas 2 --traffic ... --slo ...`` serves a mixed
    two-class stream from it through the router — the JSON contract
    carries per-class completion/status tallies (the chaos-chain
    assertion surface), the router summary with per-replica placements,
    and per-completion traffic classes."""
    d = str(tmp_path / "ck")
    model = ["--vocab", "16", "--d-model", "32", "--heads", "2",
             "--layers", "2", "--d-ff", "64"]
    assert main(["lm", "--num-workers", "1", "--seq-scheme", "full",
                 "--seq-len", "16", "--train-seqs", "32", "--test-seqs",
                 "8", "--batch-size", "16", "--eval-every", "2",
                 "--checkpoint-dir", d] + model) == 0
    capsys.readouterr()
    assert main([
        "serve", "--replicas", "2", "--checkpoint-dir", d, "--slots", "2",
        "--capacity", "64", "--prefix-cache", "2", "--shed-threshold", "4",
        "--traffic",
        "horizon=8;max_requests=8;seed=5;"
        "chat:rate=0.9,pmin=6,pmax=10,new=2,families=2,fprefix=4;"
        "bulk:rate=0.5,pmin=6,pmax=10,new=2",
        "--slo", "chat:ttft=30,priority=0;bulk:ttft=60,priority=2",
        "--json"] + model) == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload["variant"] == "serve" and payload["replicas"] == 2
    assert len(payload["completions"]) == 8
    classes = {c["traffic_class"] for c in payload["completions"].values()}
    assert classes <= {"chat", "bulk"} and len(classes) == 2
    tallies = payload["per_class"]
    assert sum(row["total"] for row in tallies.values()) == 8
    for row in tallies.values():
        assert row["total"] == row["ok"] + row["shed"] \
            + row["deadline_exceeded"]
    router = payload["router"]
    assert len(router["per_replica_requests"]) == 2
    assert sum(router["per_replica_requests"]) + router["router_sheds"] == 8
    assert set(router["per_class"]) == classes
    for row in router["per_class"].values():
        assert 0.0 <= row["ttft_slo_attained"] <= 1.0


def test_main_serve_router_flag_hygiene():
    """Router flag hygiene both directions: --traffic/--slo without
    --replicas fail loudly, router flags fail on training variants,
    bare-prompt-set flags fail under --replicas, and malformed specs
    are config errors."""
    with pytest.raises(SystemExit, match="--traffic requires --replicas"):
        main(["serve", "--platform", "cpu", "--traffic", "chat:rate=1"])
    with pytest.raises(SystemExit, match="--slo requires --replicas"):
        main(["serve", "--platform", "cpu", "--slo", "chat:ttft=1"])
    with pytest.raises(SystemExit, match="--replicas"):
        main(["lm", "--replicas", "2"])
    with pytest.raises(SystemExit, match="--num-prompts does not apply"):
        main(["serve", "--platform", "cpu", "--replicas", "2",
              "--num-prompts", "5"])
    with pytest.raises(SystemExit, match="serve config error"):
        main(["serve", "--platform", "cpu", "--replicas", "2",
              "--traffic", "chat:rate=1,nope=3"])
    with pytest.raises(SystemExit, match="serve config error"):
        main(["serve", "--platform", "cpu", "--replicas", "2",
              "--traffic", "chat:rate=1,pmin=8,pmax=300,new=8"])
    with pytest.raises(SystemExit, match="serve config error"):
        main(["serve", "--platform", "cpu", "--replicas", "2",
              "--slo", "nope:ttft=1"])
    with pytest.raises(SystemExit, match="--replicas must be >= 1"):
        main(["serve", "--platform", "cpu", "--replicas", "0"])


def test_main_serve_autoscale_end_to_end(capsys):
    """ISSUE 13 CLI surface: ``--autoscale`` + ``--max-replicas`` on a
    bursty stream scales the fleet out and back in; the JSON contract
    carries the controller digest (scale events, drains, the event
    ledger) under router.fleet, and every request resolves to a final
    status."""
    model = ["--vocab", "16", "--d-model", "32", "--heads", "2",
             "--layers", "2", "--d-ff", "64"]
    assert main([
        "serve", "--platform", "cpu", "--replicas", "1", "--slots", "1",
        "--capacity", "64", "--shed-threshold", "2",
        "--autoscale", "backlog=2,sustain=2,idle=4", "--max-replicas", "2",
        "--slo", "bulk:priority=1,margin=1",
        "--traffic",
        "horizon=12;seed=0;max_requests=10;burst=3:4:5.0:bulk;"
        "chat:rate=0.3,pmin=4,pmax=8,new=2;"
        "bulk:rate=0.4,pmin=4,pmax=8,new=2",
        "--json"] + model) == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    fleet = payload["router"]["fleet"]
    assert fleet["max_replicas"] == 2
    assert fleet["scale_outs"] >= 1 and fleet["scale_ins"] >= 1
    assert fleet["crashes"] == 0
    kinds = [e["kind"] for e in fleet["events"]]
    assert "scale_out" in kinds and "drain" in kinds
    for row in payload["per_class"].values():
        assert row["total"] == row["ok"] + row["shed"] \
            + row["deadline_exceeded"]


def test_main_serve_autoscale_flag_hygiene():
    """Fleet flag hygiene: --autoscale needs --replicas, --max-replicas
    needs --autoscale, replica_crash needs the controller, and
    malformed autoscale specs are named config errors."""
    with pytest.raises(SystemExit, match="--autoscale requires --replicas"):
        main(["serve", "--platform", "cpu", "--autoscale", "backlog=2"])
    with pytest.raises(SystemExit,
                       match="--max-replicas requires --autoscale"):
        main(["serve", "--platform", "cpu", "--max-replicas", "2"])
    with pytest.raises(SystemExit, match="--autoscale"):
        main(["lm", "--autoscale", "backlog=2"])
    with pytest.raises(SystemExit, match="fleet cap"):
        main(["serve", "--platform", "cpu", "--replicas", "1",
              "--autoscale", "backlog=2"])
    with pytest.raises(SystemExit, match="unknown autoscale key"):
        main(["serve", "--platform", "cpu", "--replicas", "1",
              "--autoscale", "frob=1", "--max-replicas", "2"])
    with pytest.raises(SystemExit, match="replica_crash needs --autoscale"):
        main(["serve", "--platform", "cpu", "--replicas", "2",
              "--inject-fault", "replica_crash@3:1"])
    with pytest.raises(SystemExit, match="applies to the serve variant"):
        main(["lm", "--inject-fault", "replica_crash@3:1"])


def test_main_serve_rejects_bad_prefix_chunk_flags():
    """Flag hygiene both ways: serve-only prefix/chunk flags fail
    loudly on training variants, and invalid combinations fail as
    config errors, not deep tracebacks."""
    with pytest.raises(SystemExit, match="--prefix-cache"):
        main(["lm", "--prefix-cache", "2"])
    with pytest.raises(SystemExit, match="--prefill-chunk"):
        main(["sync", "--prefill-chunk", "8"])
    with pytest.raises(SystemExit, match="serve config error"):
        main(["serve", "--platform", "cpu", "--prefill-chunk", "12"])
    with pytest.raises(SystemExit, match="serve config error"):
        main(["serve", "--platform", "cpu", "--prefill-budget", "16"])
    # Paged flag hygiene (ISSUE 7), both directions: geometry errors
    # are loud config errors; --num-pages without --page-size too.
    with pytest.raises(SystemExit, match="serve config error"):
        main(["serve", "--platform", "cpu", "--page-size", "12"])
    with pytest.raises(SystemExit, match="serve config error"):
        main(["serve", "--platform", "cpu", "--num-pages", "8"])
    with pytest.raises(SystemExit, match="serve config error"):
        main(["serve", "--platform", "cpu", "--page-size", "8",
              "--num-pages", "2"])  # below --slots (default 4)


def test_main_serve_disagg_speculate_end_to_end(capsys):
    """ISSUE 15 CLI surface: ``--roles`` + ``--speculate`` on a paged
    router fleet serves the stream disaggregated AND speculative — the
    JSON contract carries the disagg digest (role split, hand-off
    ledger) and the speculation acceptance digest, and every request
    resolves ok."""
    model = ["--vocab", "16", "--d-model", "32", "--heads", "2",
             "--layers", "2", "--d-ff", "64"]
    assert main([
        "serve", "--platform", "cpu", "--replicas", "2", "--slots", "2",
        "--capacity", "64", "--page-size", "8",
        "--roles", "prefill=1,decode=1", "--speculate", "2",
        "--traffic",
        "horizon=8;seed=0;max_requests=6;"
        "chat:rate=0.6,pmin=4,pmax=8,new=6",
        "--metrics-out", "/dev/null", "--json"] + model) == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    disagg = payload["router"]["disagg"]
    assert disagg["roles"] == {"prefill": 1, "decode": 1}
    assert disagg["handoffs"] >= 1
    assert disagg["handoff_pages"] >= disagg["handoffs"]
    spec = payload["speculate"]
    assert spec["k"] == 2 and spec["method"] == "ngram"
    assert 0 <= spec["accepted"] <= spec["proposed"]
    for row in payload["per_class"].values():
        assert row["total"] == row["ok"]


def test_main_serve_disagg_speculate_flag_hygiene():
    """ISSUE 15 flag hygiene BOTH WAYS: --roles/--speculate without
    --replicas or on contiguous engines reject loudly with the
    offending combination named; malformed specs are named errors; the
    flags fail on training variants."""
    with pytest.raises(SystemExit, match="--roles .* requires --replicas"):
        main(["serve", "--platform", "cpu",
              "--roles", "prefill=1,decode=1"])
    with pytest.raises(SystemExit,
                       match="--roles .* requires --page-size"):
        main(["serve", "--platform", "cpu", "--replicas", "2",
              "--roles", "prefill=1,decode=1"])
    with pytest.raises(SystemExit,
                       match="--speculate 4 requires --replicas"):
        main(["serve", "--platform", "cpu", "--speculate", "4"])
    with pytest.raises(SystemExit,
                       match="--speculate 4 requires --page-size"):
        main(["serve", "--platform", "cpu", "--replicas", "2",
              "--speculate", "4"])
    with pytest.raises(SystemExit, match="sum to it"):
        main(["serve", "--platform", "cpu", "--replicas", "2",
              "--page-size", "8", "--roles", "prefill=1,decode=2"])
    with pytest.raises(SystemExit, match="no decode-"):
        main(["serve", "--platform", "cpu", "--replicas", "2",
              "--page-size", "8", "--roles", "prefill=2"])
    with pytest.raises(SystemExit, match="draft length"):
        main(["serve", "--platform", "cpu", "--replicas", "2",
              "--page-size", "8", "--speculate", "zero"])
    with pytest.raises(SystemExit, match="unknown method"):
        main(["serve", "--platform", "cpu", "--replicas", "2",
              "--page-size", "8", "--speculate", "4,beam"])
    with pytest.raises(SystemExit, match="--roles"):
        main(["lm", "--roles", "prefill=1,decode=1"])
    with pytest.raises(SystemExit, match="--speculate"):
        main(["lm", "--speculate", "4"])
    # Deep engine validation still surfaces as a config error: greedy
    # is required for greedy-accept.
    with pytest.raises(SystemExit, match="serve config error"):
        main(["serve", "--platform", "cpu", "--replicas", "2",
              "--page-size", "8", "--speculate", "2",
              "--temperature", "0.8"])
