"""Command-line launcher — the TPU replacement for the reference's six
``run.sh`` scripts (reference: mnist_sync/run.sh:3 expands
``mpiexec -n $1 parameter_server.py : -n $2 worker.py``; SURVEY.md §1
"launcher layer").

One process drives all chips (JAX single-controller) — there is no MPMD
role split; the PS/worker topology becomes a strategy config:

    python -m ddl_tpu single
    python -m ddl_tpu sync                  --num-workers 8
    python -m ddl_tpu async                 --num-workers 8
    python -m ddl_tpu sync_sharding         --num-ps 4 --num-workers 8
    python -m ddl_tpu async_sharding        --num-ps 4 --num-workers 8
    python -m ddl_tpu sync_sharding_greedy  --num-ps 4 --num-workers 8
    python -m ddl_tpu async_sharding_greedy --num-ps 4 --num-workers 8

The reference invocation ``run.sh <num_ps> <num_workers>`` maps to
``--num-ps <num_ps> --num-workers <num_workers>``. Extra capabilities the
reference hardcodes are flags here (epochs, batch size, LR, layout policy,
compat switches — see ddl_tpu.train.config.TrainConfig).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys

VARIANTS = (
    "single",
    "sync",
    "async",
    "sync_sharding",
    "async_sharding",
    "sync_sharding_greedy",
    "async_sharding_greedy",
    # Beyond the reference matrix: sequence-parallel LM training (ring /
    # Ulysses attention over the mesh; strategies/seq.py). The reference
    # has no sequence axis anywhere (SURVEY.md §5).
    "lm",
    # The inference half: KV-cache autoregressive decode with tp-sharded
    # continuous batching (ddl_tpu.serve) — loads params-only from any
    # trained topology's checkpoint.
    "serve",
    # The digital twin (ISSUE 18): replay a named scenario from
    # ddl_tpu.serve.scenarios on the cost-model engine (serve.sim) —
    # the REAL router/scheduler/controller control plane over engines
    # that charge fitted virtual time instead of computing. Tick-for-
    # tick decision parity with the real fleet; million-request scale
    # on a laptop CPU.
    "sim",
)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ddl_tpu",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("variant", choices=VARIANTS)
    p.add_argument("--num-workers", type=int, default=None,
                   help="data-parallel degree (default: all devices)")
    p.add_argument("--num-ps", type=int, default=2,
                   help="parameter shard count for *_sharding variants "
                        "(reference run.sh arg $1; any split works — more "
                        "shards than workers fold round-robin onto the mesh, "
                        "and var-granular layouts clamp to one shard per "
                        "variable beyond num_vars)")
    p.add_argument("--layout", default=None,
                   choices=["block", "zigzag", "lpt", "flat"],
                   help="shard layout policy (default: block for *_sharding, "
                        "zigzag for *_greedy; '--layout flat --num-ps "
                        "<num-workers>' is the TPU-native ZeRO-1 fast path)")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--batch-size", type=int, default=None,
                   help="global batch size (reference default 100; when "
                        "unset, rounded up to a multiple of --num-workers "
                        "so sharded data divides evenly)")
    p.add_argument("--lr", type=float, default=None,
                   help="Adam learning rate (default: 1e-4, the reference's "
                        "model.py:93; lm: 1e-3)")
    p.add_argument("--keep-prob", type=float, default=0.5)
    p.add_argument("--eval-every", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--staleness-seed", type=int, default=0)
    p.add_argument("--data", default="data/mnist.pkl",
                   help="mnist.pkl path; synthesized procedurally if absent")
    p.add_argument("--synthetic-train", type=int, default=50_000,
                   help="procedural train-set size when --data is absent")
    p.add_argument("--synthetic-test", type=int, default=10_000,
                   help="procedural test-set size when --data is absent")
    p.add_argument("--bf16", action="store_true",
                   help="force bfloat16 compute (MXU fast path; the "
                        "DEFAULT when the active platform is TPU)")
    p.add_argument("--fp32", action="store_true",
                   help="force fp32 compute (strict reference-numerics "
                        "parity; the default off-TPU)")
    p.add_argument("--precision", default=None, choices=["fp32", "bf16"],
                   help="first-class precision policy (ddl_tpu.precision): "
                        "fp32 = today's programs byte-identical; bf16 = "
                        "bf16 activations AND gradient reductions with "
                        "fp32 master weights/Adam moments (arXiv "
                        "2204.06514). Owns the compute dtype — mutually "
                        "exclusive with --bf16/--fp32 (which keep their "
                        "legacy compute-only semantics)")
    p.add_argument("--kv-dtype", default=None, choices=["int8"],
                   help="serve: KV-POOL storage dtype (requires "
                        "--page-size). int8 stores pool pages as int8 "
                        "with per-head fp32 scales — ~2x pages per HBM "
                        "byte, half the bytes through every page "
                        "dump/load hand-off (preemption, crash requeue, "
                        "disagg); dequantized in the attend view")
    p.add_argument("--fused-adam", action="store_true",
                   help="use the hand-fused Pallas Adam kernel for the "
                        "sharded update (default: XLA-fused; see "
                        "benchmarks/adam_kernel.py for the comparison)")
    p.add_argument("--conv1-matmul", action="store_true",
                   help="lower the 1-input-channel first conv as an "
                        "explicit patches-matmul (MXU lane utilization; "
                        "1e-5-level numerics difference — measured vs the "
                        "conv lowering by benchmarks/step_anatomy.py)")
    p.add_argument("--conv-matmul", default="none",
                   choices=["none", "first", "tail", "first+tail", "all"],
                   help="which conv stages run as explicit patches-matmuls: "
                        "first (= --conv1-matmul), tail (convs 3-4 — the "
                        "small-spatial stages whose conv-kernel fixed cost "
                        "dominates small-batch step time), all; measured "
                        "head-to-head by benchmarks/step_anatomy.py")
    p.add_argument("--conv-channels", type=_int_tuple, default=None,
                   metavar="C1,C2,C3,C4",
                   help="conv widths of the model family (default "
                        "32,64,128,256 — the reference architecture)")
    p.add_argument("--fc-sizes", type=_int_tuple, default=None,
                   metavar="F1,F2",
                   help="FC widths of the model family (default 1024,512)")
    p.add_argument("--tiny", action="store_true",
                   help="narrow model preset (--conv-channels 4,8,8,8 "
                        "--fc-sizes 32,16): structurally identical 14-var "
                        "model at ~1/400 the FLOPs, for smoke runs and CI")
    p.add_argument("--reference-compat", action="store_true",
                   help="reproduce the reference's accidental semantics: "
                        "summed (not averaged) gradients and identical "
                        "batches on every worker")
    p.add_argument("--checkpoint-dir", default=None,
                   help="save an atomic rolling checkpoint (params + "
                        "optimizer state) at every epoch end — the "
                        "persistence the reference lacks entirely "
                        "(params die with the TF session, model.py:109-112)")
    p.add_argument("--checkpoint-every", type=int, default=0, metavar="N",
                   help="additionally checkpoint every N batches "
                        "(async: N rounds); 0 = epoch end only")
    p.add_argument("--resume", nargs="?", const="latest", default=None,
                   choices=["latest", "auto"], metavar="MODE",
                   help="resume from --checkpoint-dir: bare --resume (or "
                        "'latest') loads the rolling checkpoint exactly; "
                        "'auto' discovers the newest VALID save — corrupt "
                        "or truncated files are checksum-verified out and "
                        "resume falls back to the previous retained one "
                        "(missing checkpoint starts fresh either way)")
    p.add_argument("--max-bad-steps", type=int, default=None, metavar="K",
                   help="single/lm: compile the NaN-guarded train step "
                        "(a step with non-finite gradients applies "
                        "identity in-graph — no crash, no divergence "
                        "poisoning the optimizer state) and roll back to "
                        "the last good checkpoint after K CONSECUTIVE "
                        "skipped steps, replaying from its step "
                        "(requires --checkpoint-dir)")
    p.add_argument("--inject-fault", default=None, metavar="SPEC",
                   help="deterministic chaos (ddl_tpu.resilience.faults): "
                        "train (single/lm): nan_grads@K[xN] / "
                        "inf_grads@K[xN] (poison N batches' data from "
                        "global step K; append '!' to persist through "
                        "rollbacks), sigterm@K (real SIGTERM once step K "
                        "completes), corrupt_ckpt / truncate_ckpt (damage "
                        "the latest checkpoint at startup, then prove "
                        "--resume auto); serve: stall@REQID (never "
                        "advance that request's prefill — its deadline "
                        "must evict it)")
    p.add_argument("--dispatch-timeout", type=float, default=0.0,
                   metavar="SECONDS",
                   help="fail with a diagnosis (instead of hanging forever) "
                        "if a training span or eval does not complete in "
                        "SECONDS — accelerator-death detection; <= 0 "
                        "disables")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="capture a jax.profiler trace of the training loop "
                        "into DIR (view in TensorBoard/Perfetto)")
    p.add_argument("--metrics-out", default=None, metavar="FILE",
                   help="write run telemetry as JSONL (ddl_tpu.obs registry "
                        "snapshots — counters/gauges/histograms; the FIRST "
                        "record is a run manifest with jax/jaxlib versions, "
                        "mesh shape, config dump and git sha). On train/lm "
                        "this also enables the in-graph health signals "
                        "(grad norm, per-subtree param/update norms, "
                        "non-finite counters)")
    p.add_argument("--metrics-interval", type=int, default=None, metavar="N",
                   help="fetch the in-graph health signals every N global "
                        "steps (default 10; one batched device->host read "
                        "at a span boundary — never a per-step sync); "
                        "requires --metrics-out")
    p.add_argument("--prom-port", type=int, default=None, metavar="PORT",
                   help="serve the live metric registry over HTTP from a "
                        "stdlib daemon thread: GET /metrics returns the "
                        "Prometheus text exposition (byte-identical to the "
                        "in-process prometheus_text()), GET /healthz a "
                        "liveness JSON. PORT 0 binds an ephemeral port "
                        "(printed at startup). Works with or without "
                        "--metrics-out (a registry is created either way)")
    p.add_argument("--peak-flops", type=float, default=None, metavar="FLOPS",
                   help="per-device peak FLOP/s for the train_mfu/serve_mfu "
                        "gauges (ddl_tpu.obs.cost): overrides the built-in "
                        "device-kind table (TPU v2-v5 bf16 peaks; CPU "
                        "uses a documented nominal anchor so CPU runs "
                        "still produce a number; an accelerator kind the "
                        "table does not know is an error without this "
                        "flag)")
    p.add_argument("--ici-bw", type=float, default=None, metavar="BPS",
                   help="per-device interconnect bytes/s for the comms "
                        "roofline gauges (ddl_tpu.obs.comms): overrides the "
                        "built-in device-kind table (TPU v2-v5 nominal ICI "
                        "figures; CPU uses a documented nominal anchor so "
                        "CPU runs still produce a number; an accelerator "
                        "kind the table does not know is an error without "
                        "this flag)")
    p.add_argument("--anomaly-rules", default=None, metavar="SPEC",
                   help="streaming anomaly detection (ddl_tpu.obs.anomaly) "
                        "on the deterministic tick clock: ';'-joined "
                        "SIGNAL[:window=W,min=M,threshold=Z,direction="
                        "high|low|both,scale=S] segments — rolling "
                        "median/MAD baselines with edge-triggered "
                        "anomaly_total{signal=} counters, anomaly_last_tick "
                        "gauges and 'anomaly' trace events. Signals: serve "
                        "step_time/itl/mfu/queue_depth/active_slots/"
                        "occupied_slots/pages_free (paged), router "
                        "backlog/shed_rate, trainers step_time/mfu. "
                        "Applies to single/lm/serve")
    p.add_argument("--trace-dir", default=None, metavar="DIR",
                   help="capture a structured trace into DIR: host spans/"
                        "request-lifecycle events as host_trace_p*.jsonl "
                        "(convert to Chrome/Perfetto with 'python -m "
                        "ddl_tpu.obs.trace in.jsonl out.json', analyze "
                        "goodput/critical paths offline with 'python -m "
                        "ddl_tpu.obs.analyze report') PLUS the "
                        "jax.profiler XLA timeline in the same directory")
    p.add_argument("--json", action="store_true",
                   help="emit a single JSON result line at exit")
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                   help="force a JAX platform before backend init. "
                        "'--platform cpu' is the one way to get the "
                        "virtual CPU mesh, sized to the run's device "
                        "count (at least 8) — for CI and smoke runs. "
                        "JAX_PLATFORMS=cpu in the environment is honoured "
                        "too, with whatever device count the environment "
                        "set; too few devices is an error, never a "
                        "silent platform swap")
    lm = p.add_argument_group(
        "lm (sequence-parallel) options",
        "the 'lm' variant trains the decoder LM on the procedural copy "
        "task with the SEQUENCE axis sharded over the mesh "
        "(strategies/seq.py); --num-workers is the sequence-parallel "
        "degree, --batch-size counts sequences (default 32), --epochs/"
        "--eval-every/--seed/--bf16/--json apply as usual",
    )
    lm.add_argument("--seq-scheme", default="ring",
                    choices=["ring", "ulysses", "full"],
                    help="cross-shard attention scheme: ring (ppermute "
                         "K/V rotation), ulysses (all_to_all head "
                         "re-partition; needs --heads divisible by "
                         "--num-workers), full (no sharding; W=1 only)")
    lm.add_argument("--seq-len", type=int, default=512,
                    help="sequence length (divisible by --num-workers)")
    lm.add_argument("--vocab", type=int, default=64)
    lm.add_argument("--d-model", type=int, default=256)
    lm.add_argument("--heads", type=int, default=8)
    lm.add_argument("--layers", type=int, default=4)
    lm.add_argument("--d-ff", type=int, default=1024)
    lm.add_argument("--train-seqs", type=int, default=2048,
                    help="procedural copy-task training sequences")
    lm.add_argument("--test-seqs", type=int, default=256)
    lm.add_argument("--target-accuracy", type=float, default=None,
                    help="stop at the first eval reaching this next-token "
                         "accuracy")
    lm.add_argument("--attn-impl", default="xla", choices=["xla", "flash"],
                    help="local attention kernel: xla (einsum softmax) or "
                         "flash (Pallas flash-attention kernel on TPU — "
                         "O(T*block) score memory; pure-JAX reference "
                         "off-TPU); schemes full/ulysses only")
    lm.add_argument("--tensor-parallel", type=int, default=1, metavar="TP",
                    help="Megatron tensor parallelism: each block's "
                         "QKV/W1 shard column-wise (H/TP heads, d_ff/TP "
                         "hidden units per device), WO/W2 row-wise with "
                         "one completing psum each; 3-D mesh "
                         "[data-parallel, num-workers, TP], tp minor "
                         "(its psums ride neighbouring ICI links); "
                         "composes with --zero1 (hybrid sharded "
                         "optimizer) and with --multihost worlds")
    lm.add_argument("--remat", action="store_true",
                    help="rematerialize each transformer block in the "
                         "backward pass (jax.checkpoint): per-block saved "
                         "state drops from the attention sweep's residuals "
                         "to the block input, for ~1/3 extra FLOPs — the "
                         "long-context memory lever")
    lm.add_argument("--seq-layout", default="contiguous",
                    choices=["contiguous", "zigzag"],
                    help="ring position layout: contiguous (block i on "
                         "device i — device P-1 computes every causal ring "
                         "step) or zigzag (two-ended chunk pairs — halves "
                         "the causal critical path; scheme=ring, seq-len "
                         "divisible by 2*num-workers)")
    lm.add_argument("--data-parallel", type=int, default=1, metavar="DP",
                    help="2-D mesh: batch shards over DP rows while the "
                         "sequence shards over --num-workers columns "
                         "(total devices = DP * num-workers); --batch-size "
                         "must divide by DP")
    lm.add_argument("--pipeline-parallel", type=int, default=1,
                    metavar="PP",
                    help="pipeline parallelism (ddl_tpu.pipeline): split "
                         "the layer stack into PP contiguous stages over "
                         "the pp mesh axis (minor — stage-hop ppermutes "
                         "ride neighbouring ICI links); needs --layers "
                         "divisible by PP, --microbatches >= 2, "
                         "--num-workers 1 --seq-scheme full; composes "
                         "with --data-parallel and --tensor-parallel on "
                         "the 4-D [dp, 1, tp, pp] mesh (NOT with --zero1 "
                         "or sequence parallelism — see the README "
                         "composition matrix)")
    lm.add_argument("--microbatches", type=int, default=1, metavar="M",
                    help="microbatches streamed through the pipeline per "
                         "step (gradient-accumulated; bubble fraction = "
                         "(PP-1)/(M+PP-1)); must divide the per-dp-row "
                         "batch; requires --pipeline-parallel > 1")
    lm.add_argument("--pipeline-schedule", default="gpipe",
                    choices=["gpipe", "1f1b"],
                    help="microbatch schedule: gpipe (flush — all "
                         "forwards, then all backwards; M in-flight "
                         "activations per stage) or 1f1b (steady-state "
                         "one-forward-one-backward; min(PP, M) in-flight "
                         "— same bubble, less memory)")
    lm.add_argument("--zero1", action="store_true",
                    help="ZeRO-1 over the combined (dp, sp) mesh axes: "
                         "reduce-scatter grads, Adam on each device's "
                         "flat chunk (m/v owner-resident — optimizer "
                         "memory /(DP*num-workers)), all_gather params; "
                         "composes with any --seq-scheme, "
                         "--data-parallel, AND --tensor-parallel (the "
                         "hybrid sharded optimizer: tp-sharded weights "
                         "keep tp-local Adam state, the tp-replicated "
                         "subtree — embed/head/LayerNorms — shards its "
                         "Adam state over dp x sp)")
    sv = p.add_argument_group(
        "serve options",
        "the 'serve' variant runs KV-cache autoregressive decode with "
        "continuous batching (ddl_tpu.serve) over a deterministic "
        "seeded prompt set; the model flags (--vocab/--d-model/--heads/"
        "--layers/--d-ff), --tensor-parallel, --seed, --bf16/--fp32 and "
        "--json apply as usual; --checkpoint-dir loads params-only from "
        "a training checkpoint of ANY topology (no optimizer state "
        "required)",
    )
    sv.add_argument("--slots", type=int, default=4,
                    help="continuous-batching width: concurrent sequences "
                         "decoded per step")
    sv.add_argument("--capacity", type=int, default=256,
                    help="KV-cache rows per slot — bounds prompt + "
                         "generated length")
    sv.add_argument("--max-new-tokens", type=int, default=32,
                    help="tokens generated per request")
    sv.add_argument("--num-prompts", type=int, default=8,
                    help="size of the seeded synthetic prompt set "
                         "(data.lm.synthesize_prompts)")
    sv.add_argument("--prompt-min", type=int, default=4,
                    help="minimum synthetic prompt length")
    sv.add_argument("--prompt-max", type=int, default=48,
                    help="maximum synthetic prompt length")
    sv.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature; 0 = greedy decode")
    sv.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the k highest logits; "
                         "0 = full vocab (temperature > 0 only)")
    sv.add_argument("--prefix-cache", type=int, default=0, metavar="SLOTS",
                    help="prefix-cache pool width: retain completed "
                         "prompts' K/V rows in SLOTS dedicated cache "
                         "slots and admit new requests by copying their "
                         "longest cached prefix (refcounted LRU "
                         "eviction); 0 = off. Output tokens are "
                         "bit-identical either way — only prefill work "
                         "and TTFT change")
    sv.add_argument("--prefill-chunk", type=int, default=0, metavar="N",
                    help="chunked prefill: stream prompts in N-token "
                         "chunks interleaved with decode ticks (N a "
                         "power of two >= 8 — one extra compiled "
                         "bucket) so a long prompt stops stalling "
                         "active decoders; 0 = whole-prompt prefill")
    sv.add_argument("--prefill-budget", type=int, default=0, metavar="T",
                    help="max prefill tokens per scheduler tick when "
                         "chunking (>= --prefill-chunk); 0 = one chunk "
                         "per tick, the maximum-interleaving default")
    sv.add_argument("--page-size", type=int, default=0, metavar="ROWS",
                    help="paged KV cache: rows per page (a power of "
                         "two; --capacity must be a multiple). Replaces "
                         "the per-slot rings with one shared page pool "
                         "+ per-slot block tables: admission reserves "
                         "only the pages a request can actually use "
                         "(capacity pools across slots), prefix hits "
                         "share pages zero-copy, and decode programs "
                         "bucket on page count. Tokens are bit-identical "
                         "to the contiguous layout. 0 = contiguous "
                         "(the default, and the bit-exactness oracle)")
    sv.add_argument("--num-pages", type=int, default=0, metavar="N",
                    help="paged KV pool size in pages (requires "
                         "--page-size; must be >= --slots). 0 = "
                         "slots * capacity / page-size — the slot-major "
                         "memory envelope, no pooling savings but "
                         "drop-in; a SMALLER pool is the point: "
                         "admission becomes 'enough free pages' "
                         "instead of worst-case rows per slot")
    sv.add_argument("--model-spec", default=None, metavar="NAME",
                    help="serve a named spec of the second decoder "
                         "family (models.hybrid.NAMED_SPECS: layers of "
                         "five kinds, global, window, latent, linear and "
                         "block-sparse attention, with routed experts "
                         "where the spec has them) in place of "
                         "the dense decoder the --d-model/--heads/"
                         "--layers flags describe; needs --page-size > 0 "
                         "(page groups)")
    sv.add_argument("--ttft-deadline", type=float, default=None,
                    metavar="SECONDS",
                    help="default per-request time-to-first-token "
                         "deadline: a request not decoding within "
                         "SECONDS of becoming eligible is evicted with "
                         "status 'deadline_exceeded' (slot freed, "
                         "prefix refs released)")
    sv.add_argument("--request-deadline", type=float, default=None,
                    metavar="SECONDS",
                    help="default per-request TOTAL deadline "
                         "(eligibility to completion); expiry returns "
                         "the partial tokens with status "
                         "'deadline_exceeded'")
    sv.add_argument("--shed-threshold", type=int, default=None, metavar="N",
                    help="admission shedding: a request whose first "
                         "eligible tick finds N outstanding requests "
                         "(occupied slots + waiting eligibles) is "
                         "refused with status 'shed' instead of "
                         "collapsing admitted traffic's ITL; must be "
                         ">= --slots (with --replicas: per replica, and "
                         "the reference point class shed margins "
                         "subtract from)")
    sv.add_argument("--replicas", type=int, default=None, metavar="N",
                    help="multi-tenant front door (ddl_tpu.serve.router): "
                         "run N independent scheduler/engine replicas "
                         "(each with its own KV pool and prefix index, "
                         "sharing one checkpoint's params) behind an "
                         "SLO-aware router — prefix-affinity placement, "
                         "per-class priority shedding, per-class TTFT/ITL "
                         "accounting. Drives the --traffic stream instead "
                         "of the --num-prompts set")
    sv.add_argument("--traffic", default=None, metavar="SPEC",
                    help="mixed-traffic scenario for --replicas "
                         "(data.lm.synthesize_mixed_traffic): ';'-joined "
                         "segments — global keys horizon=N, seed=N, "
                         "max_requests=N, burst=START:LEN:MULT[:CLASS], "
                         "diurnal=AMPLITUDE:PERIOD — and class segments "
                         "NAME:rate=R,pmin=A,pmax=B,new=T"
                         "[,families=F,fprefix=L]. Default: the "
                         "three-class chat/longdoc/bulk mix at horizon 32")
    sv.add_argument("--slo-rules", default=None, metavar="SPEC",
                    help="streaming burn-rate SLO monitors "
                         "(ddl_tpu.obs.slo) evaluated once per scheduler/"
                         "router tick against the live registry: ';'-joined "
                         "NAME:metric=M,... segments with target=SECONDS "
                         "(histogram mode: samples above the target are "
                         "misses) or total=COUNTER (counter mode: metric "
                         "counts bad events, total the attempts), plus "
                         "objective=, fast=/slow= (window ticks), "
                         "threshold=, and label.K=V series selectors. "
                         "Emits slo_burn_rate{rule=,window=} gauges, "
                         "slo_alerts_total{rule=} counters and slo_alert "
                         "trace events. Under --replicas the monitor "
                         "reads the ROUTER registry: histogram rules "
                         "must target router_ttft_seconds with "
                         "label.class= (observed live per global tick); "
                         "serve_* histograms live in per-replica "
                         "registries and are invisible to it")
    sv.add_argument("--autoscale", default=None, metavar="SPEC",
                    help="self-healing fleet controller for --replicas "
                         "(ddl_tpu.serve.controller): comma-joined "
                         "key=val — max=N (fleet cap; --max-replicas "
                         "overrides), min=N (floor; default: --replicas), "
                         "backlog=F (mean outstanding per replica that "
                         "triggers scale-out), sustain=N (ticks), idle=N "
                         "(idle ticks before a drain), preempt=0|1, "
                         "wait=N/gap=N (preemption wait ticks / priority "
                         "gap), burn=RULE|RULE (--slo-rules names whose "
                         "alert condition also triggers scale-out). "
                         "Scales out on sustained pressure/burns (door "
                         "shed defers while the fleet can grow), drains "
                         "before scale-in, heals replica crashes, and "
                         "preempts cross-replica on paged engines. Empty "
                         "SPEC ('') with --max-replicas uses defaults")
    sv.add_argument("--max-replicas", type=int, default=None, metavar="N",
                    help="fleet cap for --autoscale (overrides its max= "
                         "key); every replica is a full engine — compiled "
                         "programs + its own KV pool")
    sv.add_argument("--roles", default=None, metavar="SPEC",
                    help="disaggregated prefill/decode fleet "
                         "(ddl_tpu.serve.disagg): comma-joined "
                         "ROLE=COUNT segments (prefill/decode/mixed) "
                         "summing to --replicas. Arrivals land on "
                         "prefill replicas; on first token the "
                         "finished prefix PAGES hand off to a decode "
                         "replica (the compiled whole-page write "
                         "program). Needs --replicas and --page-size "
                         "> 0, and both sides present; per-role "
                         "autoscale knobs ride in --autoscale as "
                         "ROLE.key=val")
    sv.add_argument("--speculate", default=None, metavar="K[,METHOD]",
                    help="speculative decoding "
                         "(ddl_tpu.serve.speculate): draft up to K "
                         "tokens per active slot per tick by n-gram "
                         "lookup (METHOD 'ngram' over prompt+generated "
                         "— the default — or 'prompt' for prompt-only "
                         "lookup) and verify them through FREE slots "
                         "of the one batched decode call (greedy-"
                         "accept: output is BIT-IDENTICAL to plain "
                         "greedy decode; acceptance measured as "
                         "speculate_accepted_total / "
                         "speculate_proposed_total). Needs --replicas, "
                         "--page-size > 0, temperature 0 and "
                         "--slots >= 2")
    sv.add_argument("--slo", default=None, metavar="SPEC",
                    help="per-class SLO targets/priorities for "
                         "--replicas: ';'-joined NAME:ttft=S,itl=S,"
                         "priority=P[,margin=M] segments (seconds; "
                         "priority 0 = most protected; margin defaults "
                         "to priority — how far below --shed-threshold "
                         "the class starts shedding at the router). "
                         "Unnamed classes get defaults")
    sm = p.add_argument_group(
        "sim options",
        "the 'sim' variant replays a named scenario "
        "(ddl_tpu.serve.scenarios) on the cost-model digital twin "
        "(ddl_tpu.serve.sim): the real router/scheduler/controller "
        "drive engines that charge fitted per-phase virtual time "
        "instead of computing — tick-for-tick decision parity with the "
        "real fleet at million-request scale; --replicas, --autoscale/"
        "--max-replicas, --json, --metrics-out and --trace-dir apply "
        "as on serve (topology/traffic shape flags come from the "
        "scenario, not the serve flags)",
    )
    sm.add_argument("--scenario", default=None, metavar="NAME[:K=V,..]",
                    help="scenario to replay (serve.scenarios.SCENARIOS: "
                         "bulk_burst, replica_crash, diurnal, crash_storm, "
                         "role_mix, longtail_prefix), with optional "
                         "comma-joined overrides horizon=, max_requests=, "
                         "rate_scale=, seed= (traffic scale — rejected on "
                         "pinned-request scenarios) and replicas= "
                         "(topology scale)")
    sm.add_argument("--fit", default=None, metavar="METRICS_JSONL",
                    help="fit the twin's per-phase costs from a MEASURED "
                         "run's --metrics-out file "
                         "(obs.goodput.phase_cost_fit: time_in_seconds"
                         "{phase=} over the phase's work units); default: "
                         "the documented CPU-calibrated CostModel "
                         "defaults")
    p.add_argument("--multihost", action="store_true",
                   help="join a multi-process JAX world before training "
                        "(jax.distributed over DCN — the mpiexec-MPMD "
                        "equivalent, reference run.sh:3). Run the same "
                        "command on every host with --process-id set; on a "
                        "TPU pod slice the coordinator/process args can all "
                        "be omitted (inferred from the TPU environment)")
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="multihost coordinator address (process 0's host; "
                        "default: self-hosted when --num-processes 1, "
                        "TPU-environment-inferred otherwise)")
    p.add_argument("--num-processes", type=int, default=None,
                   help="multihost world size (default: inferred)")
    p.add_argument("--process-id", type=int, default=None,
                   help="this process's rank in the multihost world "
                        "(default: inferred)")
    return p


def _int_tuple(text: str) -> tuple[int, ...]:
    try:
        return tuple(int(t) for t in text.split(","))
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected comma-separated ints, got {text!r}"
        )


def _resolve_precision(args) -> str | None:
    """The --precision policy name (None = legacy compute_dtype
    thread). A policy plus a legacy dtype flag is rejected here with
    the CLI's own exit, mirroring precision.resolve's conflict rule."""
    prec = getattr(args, "precision", None)
    if prec is not None and (args.bf16 or args.fp32):
        raise SystemExit(
            "--precision owns the compute dtype; drop --bf16/--fp32"
        )
    return prec


def _resolve_dtype(args) -> str | None:
    """Compute dtype: explicit flags win; otherwise bf16 on TPU (the MXU
    runs bf16 at ~2x fp32 throughput and the model's accuracy is
    insensitive — BASELINE.md records matching targets either way) and
    fp32 elsewhere (strict parity with the reference's fp32 numerics).
    With --precision set the POLICY owns the compute dtype — this
    resolver returns None so the config's precision.resolve sees no
    conflicting legacy thread (the TPU auto-default included: an fp32
    policy on TPU must stay fp32)."""
    if _resolve_precision(args) is not None:
        return None
    if args.bf16 and args.fp32:
        raise SystemExit("--bf16 and --fp32 are mutually exclusive")
    if args.bf16:
        return "bfloat16"
    if args.fp32:
        return None
    import jax

    if jax.devices()[0].platform == "tpu":
        print("[ddl_tpu] TPU platform: defaulting to bfloat16 compute "
              "(--fp32 for strict fp32)")
        return "bfloat16"
    return None


def config_from_args(args) -> "TrainConfig":
    from .train.config import TrainConfig

    sharded = "sharding" in args.variant
    layout = args.layout
    if layout is None:
        layout = "zigzag" if args.variant.endswith("greedy") else "block"
    num_workers = args.num_workers or _default_workers(args.variant)
    shard_data = not args.reference_compat
    # Sync strategies shard the global batch over workers; validate/derive
    # divisibility here so misconfiguration fails fast with a fix, not deep
    # inside the trainer (the reference hardcodes batch 100 and never shards
    # data, so it cannot hit this — worker.py:41-42).
    batch_size = args.batch_size
    if batch_size is None:
        batch_size = 100
        # Async lays data out [rounds, W, bs, ...] — bs is per-push, never
        # split across workers — so only sync needs the divisible default.
        if shard_data and args.variant.startswith("sync"):
            batch_size = -(-100 // num_workers) * num_workers  # round up
            if batch_size != 100:
                print(f"[ddl_tpu] batch size 100 -> {batch_size} "
                      f"(divisible by {num_workers} workers)")
    elif (shard_data and args.variant.startswith("sync")
          and batch_size % num_workers):
        raise SystemExit(
            f"--batch-size {batch_size} is not divisible by "
            f"{num_workers} workers (data is sharded per worker). Use a "
            f"multiple of {num_workers}, drop --batch-size to auto-round, "
            f"or pass --reference-compat for replicated data."
        )
    if args.fused_adam and not (
        sharded and args.variant.startswith("sync") and args.num_ps > 1
    ):
        raise SystemExit(
            "--fused-adam applies to the ZeRO-1 sharded sync update only "
            "(sync_sharding / sync_sharding_greedy with --num-ps >= 2); "
            "other variants (and num_ps <= 1, which is pure DP) use "
            "different update programs and would silently ignore it"
        )
    conv_channels = args.conv_channels
    fc_sizes = args.fc_sizes
    if args.tiny:
        from .models.cnn import TINY_CONV_CHANNELS, TINY_FC_SIZES

        conv_channels = conv_channels or TINY_CONV_CHANNELS
        fc_sizes = fc_sizes or TINY_FC_SIZES
    if conv_channels is not None and (
        len(conv_channels) != 4 or min(conv_channels) < 1
    ):
        raise SystemExit("--conv-channels takes exactly 4 positive widths")
    if fc_sizes is not None and (len(fc_sizes) != 2 or min(fc_sizes) < 1):
        raise SystemExit("--fc-sizes takes exactly 2 positive widths")
    return TrainConfig(
        epochs=args.epochs,
        batch_size=batch_size,
        learning_rate=args.lr if args.lr is not None else 1e-4,
        keep_prob=args.keep_prob,
        eval_every=args.eval_every,
        seed=args.seed,
        num_workers=num_workers,
        num_ps=args.num_ps if sharded else 1,
        layout=layout,
        grad_reduction="sum" if args.reference_compat else "mean",
        shard_data=shard_data,
        staleness_seed=args.staleness_seed,
        compute_dtype=_resolve_dtype(args),
        precision=_resolve_precision(args),
        fused_adam=args.fused_adam,
        conv1_matmul=args.conv1_matmul,
        conv_matmul=args.conv_matmul,
        conv_channels=conv_channels or (32, 64, 128, 256),
        fc_sizes=fc_sizes or (1024, 512),
    )


def _default_workers(variant: str) -> int:
    if variant == "single":
        return 1
    import jax

    try:
        return len(jax.devices())
    except RuntimeError as e:
        raise SystemExit(
            f"could not initialize the default JAX platform ({e}); "
            "pass --platform cpu for a virtual mesh"
        )


def _ensure_devices(n: int) -> None:
    """Exit unless the active platform has ``n`` devices. A shortfall —
    or a backend that does not initialize — is an error on every
    variant: the virtual CPU mesh is chosen and sized by ``--platform
    cpu`` only, never swapped in behind a run that asked for chips."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(
            f"this run needs {n} device(s) but the JAX platform could not "
            f"be initialized ({e})"
        )
    if len(devices) < n:
        raise SystemExit(
            f"this run needs {n} devices but the active platform "
            f"({devices[0].platform}) has {len(devices)}; lower the "
            "worker/parallel degrees to fit, or pass --platform cpu for "
            "a virtual CPU mesh of that size"
        )


def _install_sigterm_flag(enabled: bool) -> dict:
    """Graceful preemption (preemptible TPU VMs send SIGTERM before
    reclaim; an operator's Ctrl-C is the same intent): finish the
    in-flight span, save the rolling checkpoint, flush the metrics
    writer/tracer (the CLI's ``finally`` blocks), exit 0 — a later
    --resume run continues where this one stopped. Returns the flag
    dict the trainer's ``should_stop`` closes over."""
    term = {"flag": False}
    if enabled:
        import signal

        def _handler_for(signum):
            def _on_sig(sig, frame):
                # Flag only — no IO in the handler (a print here can hit
                # CPython's reentrant-BufferedWriter guard and kill the
                # run uncheckpointed). Restoring the default lets a
                # second delivery terminate promptly if the grace
                # window is too short.
                term["flag"] = True
                signal.signal(signum, signal.SIG_DFL)

            return _on_sig

        signal.signal(signal.SIGTERM, _handler_for(signal.SIGTERM))
        signal.signal(signal.SIGINT, _handler_for(signal.SIGINT))
    return term


def _fatal_timeout(e) -> "int":
    """AcceleratorTimeout exit: the watchdogged fetch is still wedged in
    native code; a normal exit would re-enter the dead backend via
    atexit/PJRT destructors and hang anyway — report, flush, and leave
    (the AcceleratorTimeout contract, parallel/mesh.py)."""
    print(f"[ddl_tpu] FATAL: {e}", file=sys.stderr)
    sys.stderr.flush()
    sys.stdout.flush()
    import os

    os._exit(1)


# Flag-hygiene groups: every flag from another variant's group that was
# changed from its parser default is rejected, so a typo fails loudly
# instead of silently running without its effect. ONE list per group —
# the lm and serve reject lists compose from these, so adding a flag to
# a group protects every other variant at once.
_MNIST_ONLY_DESTS = (
    "num_ps", "layout", "keep_prob", "staleness_seed", "data",
    "synthetic_train", "synthetic_test", "fused_adam", "conv1_matmul",
    "conv_matmul", "conv_channels", "fc_sizes", "tiny", "reference_compat",
)
# Training-only flags (lm group + the shared training machinery): the
# serving mesh has no data/sequence axis and runs no optimizer.
_TRAIN_ONLY_DESTS = (
    "seq_scheme", "seq_len", "train_seqs", "test_seqs", "target_accuracy",
    "attn_impl", "remat", "seq_layout", "data_parallel", "zero1",
    "pipeline_parallel", "microbatches", "pipeline_schedule",
    "num_workers", "epochs", "batch_size", "lr", "eval_every",
    "checkpoint_every", "resume", "dispatch_timeout", "profile",
    "max_bad_steps",
)
_SERVE_ONLY_DESTS = (
    "slots", "capacity", "max_new_tokens", "num_prompts", "prompt_min",
    "prompt_max", "temperature", "top_k", "prefix_cache", "prefill_chunk",
    "prefill_budget", "ttft_deadline", "request_deadline", "shed_threshold",
    "replicas", "traffic", "slo", "slo_rules", "autoscale", "max_replicas",
    "roles", "speculate", "model_spec",
)
_SIM_ONLY_DESTS = ("scenario", "fit")
# Serve flags whose job the SCENARIO definition does on the sim variant
# (topology, traffic shape, per-request policy): changed-from-default
# values reject loudly instead of silently losing to the scenario.
# --replicas / --autoscale / --max-replicas stay live — they are the
# twin's scale and policy-sweep knobs.
_SIM_REJECT_DESTS = tuple(
    d for d in _SERVE_ONLY_DESTS
    if d not in ("replicas", "autoscale", "max_replicas")
)


def _build_obs(args, *, config=None, mesh=None, make_tracer=True):
    """``(registry, writer, tracer)`` from the shared telemetry flags
    (ISSUE 5) — ``None`` where off. The run manifest (versions, mesh
    shape, config dump, git sha) is written as the metrics file's FIRST
    record at construction, so even a crashed run leaves an attributable
    artifact. ``make_tracer=False`` leaves the tracer to the caller
    (the serve path builds its own via ``obs.trace.trace_context``,
    which also scopes the jax.profiler trace; the trainers compose the
    pieces directly because their profiler bracket must exclude AOT
    compilation)."""
    registry = writer = tracer = None
    # A registry exists whenever anything consumes it live: the JSONL
    # writer, the /metrics pull endpoint, an SLO monitor (ISSUE 10), or
    # an anomaly detector (ISSUE 11) — all but the first work without
    # --metrics-out.
    if args.metrics_out or args.prom_port is not None \
            or getattr(args, "slo_rules", None) \
            or getattr(args, "anomaly_rules", None):
        from .obs import MetricRegistry

        registry = MetricRegistry()
    if args.metrics_out:
        from .obs import MetricsWriter, run_manifest

        writer = MetricsWriter(
            args.metrics_out, registry,
            run_manifest(config=config, mesh=mesh,
                         extra={"variant": args.variant}),
        )
    if make_tracer and args.trace_dir:
        from .obs.trace import Tracer, host_trace_file

        tracer = Tracer(host_trace_file(args.trace_dir))
    return registry, writer, tracer


def _start_exporter(args, registry):
    """``--prom-port``: launch the /metrics + /healthz pull endpoint
    (obs.export) on the run's registry. Returns the started exporter
    (close it in the run's ``finally``) or None when the flag is off."""
    if args.prom_port is None:
        return None
    from .obs.export import MetricsExporter

    try:
        exp = MetricsExporter(registry, args.prom_port).start()
    except OSError as e:
        raise SystemExit(f"--prom-port {args.prom_port}: {e}")
    print(f"[ddl_tpu] metrics endpoint: {exp.url('/metrics')} "
          f"(healthz: {exp.url('/healthz')})")
    return exp


def _make_slo_monitor(args, registry, tracer=None):
    """``--slo-rules``: build the streaming burn-rate monitor
    (obs.slo) over the run's registry; None when the flag is off."""
    if not getattr(args, "slo_rules", None):
        return None
    from .obs.slo import SloMonitor, parse_slo_rules

    try:
        rules = parse_slo_rules(args.slo_rules)
        return SloMonitor(rules, registry, tracer=tracer)
    except ValueError as e:
        raise SystemExit(f"--slo-rules: {e}")


def _make_anomaly(args, registry, tracer=None):
    """``--anomaly-rules``: build the streaming anomaly detector
    (obs.anomaly) over the run's registry; None when the flag is
    off."""
    if not getattr(args, "anomaly_rules", None):
        return None
    from .obs.anomaly import AnomalyDetector, parse_anomaly_rules

    try:
        rules = parse_anomaly_rules(args.anomaly_rules)
        return AnomalyDetector(rules, registry, tracer=tracer)
    except ValueError as e:
        raise SystemExit(f"--anomaly-rules: {e}")


def _anomaly_report(detector):
    """End-of-run ``--anomaly-rules`` surface, shared by every wired
    variant: one line per signal, returns the JSON digest (None
    without a detector)."""
    if detector is None:
        return None
    digest = detector.summary()
    for signal in sorted(digest):
        row = digest[signal]
        ticks = row["fired_ticks"]
        print(f"anomaly signal {signal}: {row['alerts']} alerts"
              f"{' at ticks ' + str(ticks) if ticks else ''}")
    return digest


def _slo_report(monitor):
    """End-of-run ``--slo-rules`` surface, shared by the single-engine
    and router serve paths: print one line per rule and return the
    JSON digest dict (None without a monitor)."""
    if monitor is None:
        return None
    digest = {}
    for name in sorted(r.name for r in monitor.rules):
        row = {
            "fast_burn": monitor.burn_rate(name, "fast"),
            "slow_burn": monitor.burn_rate(name, "slow"),
            "alerts": monitor.alerts(name),
            "fired_ticks": monitor.fired_ticks(name),
        }
        digest[name] = row
        print(f"slo rule {name}: burn fast {row['fast_burn']:.2f} slow "
              f"{row['slow_burn']:.2f} | alerts {row['alerts']}")
    return digest


def _make_injector(args, variant: str):
    """Resolve ``--inject-fault`` for this variant: validates the
    kind/variant pairing, applies startup checkpoint chaos
    (corrupt/truncate the latest save in --checkpoint-dir — pair with
    ``--resume auto`` to prove recovery), and returns a runtime
    ``FaultInjector`` for the kinds the trainer/scheduler consumes
    (None when no runtime fault is armed)."""
    if not args.inject_fault:
        return None
    from .resilience import faults

    try:
        spec = faults.parse_fault(args.inject_fault)
    except ValueError as e:
        raise SystemExit(f"--inject-fault: {e}")
    if spec.kind in faults.SERVE_KINDS:
        if variant != "serve":
            raise SystemExit(
                f"--inject-fault {spec.kind} applies to the serve variant"
            )
        return faults.FaultInjector(spec)
    if variant not in ("single", "lm"):
        raise SystemExit(
            f"--inject-fault {spec.kind} applies to the single/lm "
            "variants (the guarded trainers)"
        )
    if spec.kind in faults.CKPT_KINDS:
        from .train.trainer import checkpoint_file
        from .utils.checkpoint import find_latest_valid

        if not args.checkpoint_dir:
            raise SystemExit(
                f"--inject-fault {spec.kind} needs --checkpoint-dir"
            )
        found = find_latest_valid(args.checkpoint_dir)
        target = found[0] if found else checkpoint_file(args.checkpoint_dir)
        import os

        if not os.path.exists(target):
            raise SystemExit(
                f"--inject-fault {spec.kind}: no checkpoint at {target}"
            )
        if spec.kind == "corrupt_ckpt":
            faults.corrupt_checkpoint(target, seed=args.seed)
        else:
            faults.truncate_checkpoint(target)
        print(f"[ddl_tpu] chaos: {spec.kind} applied to {target}")
        return None
    return faults.FaultInjector(spec)


def _reject_foreign_flags(args, variant: str, dests) -> None:
    defaults = build_parser()
    for dest in dests:
        if getattr(args, dest) != defaults.get_default(dest):
            raise SystemExit(
                f"--{dest.replace('_', '-')} does not apply to the "
                f"{variant} variant"
            )


def _run_lm(args) -> int:
    """The ``lm`` variant: sequence-parallel decoder-LM training on the
    procedural copy task (platform/multihost setup already done by
    ``main``). Reuses the shared flags; MNIST-only and serve-only flags
    fail loudly (see ``_reject_foreign_flags``)."""
    _reject_foreign_flags(args, "lm", _MNIST_ONLY_DESTS + _SERVE_ONLY_DESTS
                           + _SIM_ONLY_DESTS)
    if args.resume and not args.checkpoint_dir:
        raise SystemExit("--resume requires --checkpoint-dir")
    from .data.lm import synthesize_copy
    from .models.transformer import LMSpec
    from .strategies.seq import SeqConfig, SeqTrainer

    if args.data_parallel < 1:
        raise SystemExit(f"--data-parallel must be >= 1, got {args.data_parallel}")
    if args.tensor_parallel < 1:
        raise SystemExit(
            f"--tensor-parallel must be >= 1, got {args.tensor_parallel}"
        )
    if args.pipeline_parallel < 1:
        raise SystemExit(
            f"--pipeline-parallel must be >= 1, got {args.pipeline_parallel}"
        )
    if args.num_workers:
        num_workers = args.num_workers
    elif args.pipeline_parallel > 1:
        # Pipeline topologies have no sequence axis (validate_topology
        # requires num_workers == 1) — never default it to spare devices.
        num_workers = 1
    else:
        # Default: all devices, split between the dp rows and tp columns.
        num_workers = max(
            1,
            _default_workers(args.variant)
            // (args.data_parallel * args.tensor_parallel),
        )
    n_dev = (num_workers * args.data_parallel * args.tensor_parallel
             * args.pipeline_parallel)
    _ensure_devices(n_dev)
    spec = LMSpec(vocab=args.vocab, d_model=args.d_model,
                  num_heads=args.heads, num_layers=args.layers,
                  d_ff=args.d_ff)
    scheme = args.seq_scheme
    if args.pipeline_parallel > 1 and scheme == "ring":
        # Mirror the num_workers=1 defaulting above: pipeline stages
        # hold the WHOLE sequence, so the parser's ring default maps to
        # the stage-local full-sequence kernel — loudly, never silently
        # (an explicit --seq-scheme ulysses still fails validation).
        print("[ddl_tpu] --pipeline-parallel: sequence is whole per "
              "stage; using --seq-scheme full")
        scheme = "full"
    cfg = SeqConfig(
        epochs=args.epochs,
        batch_size=args.batch_size or 32,
        learning_rate=args.lr if args.lr is not None else 1e-3,
        eval_every=args.eval_every,
        seed=args.seed,
        num_workers=num_workers,
        data_parallel=args.data_parallel,
        tensor_parallel=args.tensor_parallel,
        scheme=scheme,
        compute_dtype=_resolve_dtype(args),
        precision=_resolve_precision(args),
        target_accuracy=args.target_accuracy,
        zero1=args.zero1,
        attn_impl=args.attn_impl,
        remat=args.remat,
        seq_layout=args.seq_layout,
        pipeline_parallel=args.pipeline_parallel,
        microbatches=args.microbatches,
        pipeline_schedule=args.pipeline_schedule,
        spec=spec,
    )
    from .parallel.mesh import AcceleratorTimeout

    injector = _make_injector(args, "lm")
    term = _install_sigterm_flag(bool(args.checkpoint_dir))
    try:
        dataset = synthesize_copy(
            num_train=args.train_seqs, num_test=args.test_seqs,
            seq_len=args.seq_len, vocab=args.vocab, seed=args.seed,
        )
        trainer = SeqTrainer(cfg, dataset)
    except ValueError as e:
        # Config-shaped errors (odd seq_len, tiny vocab, indivisible
        # shards, batch > dataset) become clean CLI failures. ONLY
        # construction is guarded: every config pre-flight lives in
        # SeqTrainer.__init__, so a ValueError escaping train() below is
        # a real runtime bug (corrupt checkpoint, JAX shape error) and
        # keeps its traceback (round-4 advisor).
        raise SystemExit(f"lm config error: {e}")
    registry, writer, tracer = _build_obs(args, config=cfg, mesh=trainer.mesh)
    detector = _make_anomaly(args, registry, tracer)
    exporter = _start_exporter(args, registry)
    try:
        result = trainer.train(
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
            resume=args.resume,
            # --trace-dir captures the XLA timeline alongside the host
            # spans (an explicit --profile dir wins for the profiler).
            profile_dir=args.profile or args.trace_dir,
            should_stop=lambda: term["flag"],
            dispatch_timeout=args.dispatch_timeout,
            metrics=registry,
            metrics_interval=args.metrics_interval,
            metrics_writer=writer,
            tracer=tracer,
            max_bad_steps=args.max_bad_steps or 0,
            fault_injector=injector,
            peak_flops=args.peak_flops,
            ici_bw=args.ici_bw,
            anomaly_detector=detector,
        )
        if registry is not None:
            registry.gauge("train_final_accuracy").set(result.final_accuracy)
            registry.gauge("train_run_tokens_per_sec").set(
                result.tokens_per_sec
            )
    except AcceleratorTimeout as e:
        return _fatal_timeout(e)
    finally:
        # Close on ANY exit path with a live interpreter, so a crashed
        # run still ends with a forced final snapshot (the timeout path
        # os._exits by contract — its backend is wedged in native code).
        if exporter is not None:
            exporter.close()
        if tracer is not None:
            tracer.close()
        if writer is not None:
            writer.close()
    anomaly_digest = _anomaly_report(detector)
    print(f"training time: {result.train_time_s:.2f}s "
          f"({result.tokens_per_sec:.0f} tokens/s, "
          f"compile {result.compile_time_s:.1f}s excluded)")
    if args.json:
        print(json.dumps({
            "variant": "lm",
            "anomaly_rules": anomaly_digest,
            "config": {**dataclasses.asdict(cfg),
                       "seq_len": args.seq_len,
                       "train_seqs": args.train_seqs},
            "final_accuracy": result.final_accuracy,
            "final_loss": result.final_loss,
            "history": [[e, b, round(a, 6)] for e, b, a in result.history],
            "train_time_s": result.train_time_s,
            "tokens_per_sec": result.tokens_per_sec,
            "compile_time_s": result.compile_time_s,
            "step_stats": dataclasses.asdict(result.step_stats)
                          if result.step_stats else None,
            "resumed_from_step": result.resumed_from_step,
            "preempted": result.preempted,
            "skipped_steps": result.skipped_steps,
            "rollbacks": result.rollbacks,
        }))
    return 0


def _parse_speculate(text: str) -> tuple[int, str]:
    """``--speculate`` grammar: ``K`` or ``K,METHOD`` (methods from
    ``serve.speculate.SPECULATE_METHODS`` — ONE list, shared with the
    engine's validation). Deep validation (paged layout, greedy,
    slots) lives with the ServeConfig consumer — the engine ctor."""
    from .serve.speculate import SPECULATE_METHODS

    head, _, method = text.partition(",")
    try:
        k = int(head.strip())
    except ValueError:
        raise ValueError(f"draft length {head.strip()!r} must be an int")
    if k < 1:
        raise ValueError(f"draft length must be >= 1, got {k}")
    method = method.strip() or "ngram"
    if method not in SPECULATE_METHODS:
        raise ValueError(
            f"unknown method {method!r} "
            f"(valid: {', '.join(SPECULATE_METHODS)})"
        )
    return k, method


def _class_tallies(done, cls_of) -> dict:
    """Per-class completion/status tallies for the serve JSON (ISSUE 8
    satellite): chaos chains assert shedding hit the RIGHT class from
    this, instead of grepping completion lists."""
    out: dict = {}
    for i, c in done.items():
        row = out.setdefault(cls_of.get(i, "default"), {
            "total": 0, "ok": 0, "shed": 0, "deadline_exceeded": 0,
        })
        row["total"] += 1
        row[c.status] = row.get(c.status, 0) + 1
    return out


def _run_serve_router(args, cfg) -> int:
    """The ``--replicas`` path of the serve variant (ISSUE 8): an
    SLO-aware router (``ddl_tpu.serve.router``) over N scheduler/engine
    replicas sharing one checkpoint's params, driving the ``--traffic``
    mixed-scenario stream with per-class SLO accounting."""
    from .data.lm import DEFAULT_TRAFFIC_CLASSES, synthesize_mixed_traffic
    from .serve.router import (
        Router,
        RouterConfig,
        parse_slo_spec,
        parse_traffic_spec,
    )
    from .train.trainer import checkpoint_file

    if args.replicas < 1:
        raise SystemExit(f"--replicas must be >= 1, got {args.replicas}")
    # The bare path's prompt-set shape flags have no meaning here — the
    # per-class shapes come from --traffic. Loud-fail, not silent-ignore.
    defaults = build_parser()
    for dest in ("num_prompts", "prompt_min", "prompt_max",
                 "max_new_tokens"):
        if getattr(args, dest) != defaults.get_default(dest):
            raise SystemExit(
                f"--{dest.replace('_', '-')} does not apply with "
                "--replicas (per-class prompt/token shapes come from "
                "--traffic)"
            )
    roles = None
    if args.roles is not None:
        from .serve.disagg import parse_roles_spec

        try:
            roles = parse_roles_spec(args.roles, args.replicas)
        except ValueError as e:
            raise SystemExit(f"--roles: {e}")
    try:
        gen_kw = (parse_traffic_spec(args.traffic) if args.traffic
                  else {"classes": dict(DEFAULT_TRAFFIC_CLASSES)})
        gen_kw.setdefault("horizon", 32)
        gen_kw.setdefault("seed", args.seed)
        gen_kw.setdefault("vocab", args.vocab)
        traffic = synthesize_mixed_traffic(**gen_kw)
        class_specs = parse_slo_spec(args.slo or "",
                                     set(gen_kw["classes"]))
        rcfg = RouterConfig(
            serve=cfg, replicas=args.replicas, classes=class_specs,
            shed_threshold=args.shed_threshold,
            ttft_deadline_s=args.ttft_deadline,
            deadline_s=args.request_deadline,
            roles=roles,
        )
    except ValueError as e:
        raise SystemExit(f"serve config error: {e}")
    if not traffic:
        raise SystemExit(
            "serve config error: the --traffic scenario produced no "
            "arrivals (raise a class rate or the horizon)"
        )
    for name, spec_d in gen_kw["classes"].items():
        worst = (spec_d.get("prompt_max", 16)
                 + spec_d.get("max_new_tokens", 8))
        if worst > cfg.capacity:
            raise SystemExit(
                f"serve config error: class {name!r} worst case (pmax + "
                f"new = {worst}) exceeds --capacity {cfg.capacity}"
            )
    ckpt = checkpoint_file(args.checkpoint_dir)
    if ckpt is not None:
        import os

        if not os.path.exists(ckpt):
            raise SystemExit(f"no checkpoint at {ckpt}")
    registry, writer, _ = _build_obs(args, config=cfg, make_tracer=False)
    tracer = None
    if args.trace_dir:
        from .obs.trace import Tracer, host_trace_file

        # keep=True: the per-class SLO derivation reads the records
        # back, in addition to streaming them to the trace file.
        tracer = Tracer(host_trace_file(args.trace_dir), keep=True)
    monitor = _make_slo_monitor(args, registry, tracer)
    detector = _make_anomaly(args, registry, tracer)
    injector = _make_injector(args, "serve")
    controller = None
    if args.autoscale is not None:
        from .serve.controller import FleetController, parse_autoscale_spec

        try:
            acfg = parse_autoscale_spec(args.autoscale,
                                        max_replicas=args.max_replicas,
                                        replicas=args.replicas)
        except ValueError as e:
            raise SystemExit(f"--autoscale: {e}")
        controller = FleetController(acfg, injector=injector)
    if injector is not None and injector.spec.kind == "replica_crash" \
            and controller is None:
        raise SystemExit(
            "--inject-fault replica_crash needs --autoscale (only the "
            "fleet controller delivers the crash and heals the fleet)"
        )
    try:
        router = (
            Router.from_checkpoint(rcfg, ckpt, registry=registry,
                                   tracer=tracer, injector=injector,
                                   slo_monitor=monitor,
                                   peak_flops=args.peak_flops,
                                   anomaly_detector=detector,
                                   controller=controller)
            if ckpt is not None else
            Router(rcfg, registry=registry, tracer=tracer,
                   injector=injector, slo_monitor=monitor,
                   peak_flops=args.peak_flops,
                   anomaly_detector=detector, controller=controller)
        )
    except (ValueError, KeyError) as e:
        raise SystemExit(f"serve config error: {e}")
    if ckpt is not None:
        print(f"[ddl_tpu] serving params from {ckpt} (params-only load, "
              f"placed once for {args.replicas} replicas)")
    from .utils.metrics import trace as profiler_trace

    # Exporter starts inside the guarded block (after the ctor, which
    # can SystemExit on config errors) so no exit path leaks the bound
    # port or its daemon thread — and before warmup, so a scraper sees
    # the compile ladder's xla_compiles_total live.
    exporter = None
    try:
        exporter = _start_exporter(args, registry)
        # Compile outside the reported run (every replica may receive
        # any request, so each warms on the whole stream); the XLA
        # timeline starts after warmup, exactly like the single-engine
        # path.
        router.warmup(traffic)
        with profiler_trace(args.trace_dir):
            done, rstats = router.run(traffic)
    finally:
        if exporter is not None:
            exporter.close()
        if tracer is not None:
            tracer.close()
        if writer is not None:
            writer.close()
    slo_digest = _slo_report(monitor)
    anomaly_digest = _anomaly_report(detector)
    cls_of = {m.id: m.traffic_class for m in traffic}
    summary = rstats.summary()
    for name, row in summary["per_class"].items():
        print(f"class {name}: {row['requests']} requests -> "
              f"ok {row['ok']} shed {row['shed']} deadline "
              f"{row['deadline_exceeded']} | ttft p95 "
              f"{row['ttft_ms']['p95']:.1f}ms itl p95 "
              f"{row['itl_ms']['p95']:.1f}ms | slo attained ttft "
              f"{row['ttft_slo_attained']:.0%} itl "
              f"{row['itl_slo_attained']:.0%}")
    print(f"router: {args.replicas} replicas | placements "
          f"{summary['per_replica_requests']} (affinity "
          f"{rstats.affinity_placements}, load {rstats.load_placements}) "
          f"| router sheds {rstats.router_sheds} | prefix hit rate "
          f"{rstats.prefix_hit_rate:.0%}")
    if rstats.fleet is not None:
        fl = rstats.fleet
        print(f"fleet: max {fl['max_replicas']} | scale out "
              f"{fl['scale_outs']} in {fl['scale_ins']} (drains "
              f"{fl['drains']}) | preemptions {fl['preemptions']} | "
              f"crashes {fl['crashes']} (requeues {fl['requeues']})")
    if rstats.disagg is not None:
        dg = rstats.disagg
        role_str = " ".join(f"{r}={n}" for r, n in
                            sorted(dg["roles"].items()))
        print(f"disagg: roles {role_str} | handoffs {dg['handoffs']} "
              f"({dg['handoff_pages']} pages)")
    spec_digest = None
    if cfg.speculate_k and router.replica_registries:
        # Non-creating reads over the per-replica registries (the
        # MetricRegistry.get discipline): sum the acceptance ledger.
        prop = acc = 0
        for rg in router.replica_registries:
            for name in ("speculate_proposed_total",
                         "speculate_accepted_total"):
                c = rg.get(name)
                if c is None:
                    continue
                v = int(sum(c.value(**ls) for ls in c.label_sets()))
                if name.startswith("speculate_proposed"):
                    prop += v
                else:
                    acc += v
        spec_digest = {
            "k": cfg.speculate_k,
            "method": cfg.speculate_method,
            "proposed": prop,
            "accepted": acc,
            "acceptance": round(acc / prop, 3) if prop else None,
        }
        print(f"speculate: k={cfg.speculate_k} "
              f"({cfg.speculate_method}) | accepted {acc}/{prop} "
              f"drafts"
              + (f" ({acc / prop:.0%})" if prop else ""))
    if args.json:
        print(json.dumps({
            "variant": "serve",
            "config": dataclasses.asdict(cfg),
            "replicas": args.replicas,
            "router": summary,
            "speculate": spec_digest,
            "slo_rules": slo_digest,
            "anomaly_rules": anomaly_digest,
            "per_class": _class_tallies(done, cls_of),
            "completions": {
                str(i): {"prompt_len": done[i].prompt_len,
                         "tokens": done[i].tokens,
                         "status": done[i].status,
                         "traffic_class": cls_of.get(i, "default")}
                for i in sorted(done)
            },
        }))
    return 0


def _run_sim(args) -> int:
    """The ``sim`` variant (ISSUE 18): replay a named scenario on the
    cost-model digital twin — the REAL router/scheduler/controller
    control plane over ``serve.sim.CostModelEngine`` replicas that
    charge fitted per-phase virtual time instead of computing. Every
    routing/admission/scale/crash decision is tick-identical to the
    real fleet (tests/test_twin.py pins it); tokens are hashes and the
    clock is virtual, which is what buys million-request scale on CPU."""
    _reject_foreign_flags(args, "sim", _MNIST_ONLY_DESTS
                          + _TRAIN_ONLY_DESTS + _SIM_REJECT_DESTS)
    if args.scenario is None:
        raise SystemExit(
            "sim requires --scenario NAME[:key=value,...] (choices: "
            "bulk_burst, replica_crash, diurnal, crash_storm, role_mix, "
            "longtail_prefix)"
        )
    from .models.transformer import LMSpec
    from .obs.goodput import fleet_summary, phase_cost_fit
    from .serve.router import Router
    from .serve.scenarios import parse_scenario
    from .serve.sim import CostModel, sim_engine_factory

    try:
        scn, over = parse_scenario(args.scenario)
    except ValueError as e:
        raise SystemExit(f"--scenario: {e}")
    spec = LMSpec(vocab=args.vocab, d_model=args.d_model,
                  num_heads=args.heads, num_layers=args.layers,
                  d_ff=args.d_ff)
    cost = CostModel()
    if args.fit is not None:
        try:
            cost = CostModel.from_phase_fit(phase_cost_fit(args.fit))
        except (OSError, ValueError) as e:
            raise SystemExit(f"--fit: {e}")
    replicas = over.pop("replicas", None)
    if args.replicas is not None:
        replicas = args.replicas
    acfg = None
    if args.autoscale is not None:
        from .serve.controller import parse_autoscale_spec

        try:
            acfg = parse_autoscale_spec(
                args.autoscale, max_replicas=args.max_replicas,
                replicas=replicas if replicas is not None
                else scn.replicas,
            )
        except ValueError as e:
            raise SystemExit(f"--autoscale: {e}")
    elif args.max_replicas is not None:
        raise SystemExit(
            "--max-replicas requires --autoscale (it caps the fleet "
            "the controller may grow; pass --autoscale '' for defaults)"
        )
    try:
        traffic = scn.build_traffic(args.vocab, **over)
        rcfg = scn.router_config(
            spec, replicas=replicas,
            engine_factory=sim_engine_factory(cost),
        )
        controller = scn.make_controller(autoscale=acfg,
                                         replicas=replicas)
    except ValueError as e:
        raise SystemExit(f"sim config error: {e}")
    registry, writer, _ = _build_obs(args, config=rcfg.serve,
                                     make_tracer=False)
    tracer = None
    if args.trace_dir:
        from .obs.trace import Tracer, host_trace_file

        # keep=True: the per-class SLO derivation reads the records
        # back — a twin trace renders through the SAME obs.analyze
        # incident table as a real fleet's.
        tracer = Tracer(host_trace_file(args.trace_dir), keep=True)
    monitor = None
    if scn.slo_rule_classes:
        if registry is None:
            from .obs import MetricRegistry

            registry = MetricRegistry()
        from .obs.slo import SloMonitor

        monitor = SloMonitor(scn.slo_rules(), registry, tracer=tracer)
    exporter = None
    try:
        try:
            router = Router(rcfg, registry=registry, tracer=tracer,
                            slo_monitor=monitor, controller=controller)
        except ValueError as e:
            raise SystemExit(f"sim config error: {e}")
        exporter = _start_exporter(args, registry)
        # No warmup: the twin compiles nothing — that is the point.
        done, rstats = router.run(traffic)
    finally:
        if exporter is not None:
            exporter.close()
        if tracer is not None:
            tracer.close()
        if writer is not None:
            writer.close()
    from .serve.engine_iface import engine_kind

    vt = {"prefill": 0.0, "decode": 0.0, "handoff": 0.0, "total": 0.0}
    for eng in router.engines:
        if eng is not None and engine_kind(eng) == "sim":
            for k, v in eng.virtual_time().items():
                vt[k] += v
    summary = rstats.summary()
    print(f"sim: scenario {scn.name} | {rcfg.replicas} replicas "
          f"(cost-model twin) | {len(traffic)} requests")
    for name, row in summary["per_class"].items():
        print(f"class {name}: {row['requests']} requests -> "
              f"ok {row['ok']} shed {row['shed']} deadline "
              f"{row['deadline_exceeded']}")
    print(f"router: placements {summary['per_replica_requests']} | "
          f"router sheds {rstats.router_sheds} | prefix hit rate "
          f"{rstats.prefix_hit_rate:.0%}")
    if rstats.fleet is not None:
        fl = rstats.fleet
        print(f"fleet: max {fl['max_replicas']} | scale out "
              f"{fl['scale_outs']} in {fl['scale_ins']} (drains "
              f"{fl['drains']}) | preemptions {fl['preemptions']} | "
              f"crashes {fl['crashes']} (requeues {fl['requeues']})")
    print(f"virtual time: prefill {vt['prefill']:.3f}s decode "
          f"{vt['decode']:.3f}s handoff {vt['handoff']:.3f}s | total "
          f"{vt['total']:.3f}s")
    if args.json:
        cls_of = {m.id: m.traffic_class for m in traffic}
        print(json.dumps({
            "variant": "sim",
            "scenario": args.scenario,
            "engine_kind": "sim",
            "replicas": rcfg.replicas,
            "cost_model": dataclasses.asdict(cost),
            "router": summary,
            "virtual_time": vt,
            "slo_rules": _slo_report(monitor),
            "fleet_digest": (fleet_summary(registry)
                             if registry is not None else None),
            "per_class": _class_tallies(done, cls_of),
        }))
    return 0


def _run_serve(args) -> int:
    """The ``serve`` variant: continuous-batching KV-cache decode over a
    deterministic seeded prompt set (platform setup already done by
    ``main``). MNIST-only and training-only flags fail loudly (see
    ``_reject_foreign_flags``)."""
    _reject_foreign_flags(args, "serve",
                          _MNIST_ONLY_DESTS + _TRAIN_ONLY_DESTS
                          + _SIM_ONLY_DESTS)
    if args.multihost:
        raise SystemExit(
            "serve is single-controller (one process drives the tp mesh); "
            "--multihost does not apply"
        )
    from .data.lm import synthesize_prompts
    from .models.transformer import LMSpec
    from .serve import Request, Scheduler, ServeConfig, engine_cls
    from .train.trainer import checkpoint_file

    if args.tensor_parallel < 1:
        raise SystemExit(
            f"--tensor-parallel must be >= 1, got {args.tensor_parallel}"
        )
    _ensure_devices(args.tensor_parallel)
    spec = LMSpec(vocab=args.vocab, d_model=args.d_model,
                  num_heads=args.heads, num_layers=args.layers,
                  d_ff=args.d_ff)
    if args.model_spec is not None:
        from .models.hybrid import NAMED_SPECS

        if args.model_spec not in NAMED_SPECS:
            raise SystemExit(
                f"--model-spec {args.model_spec!r}: known specs are "
                f"{', '.join(sorted(NAMED_SPECS))}")
        if args.replicas is not None:
            raise SystemExit(
                "--model-spec serves one replica: the router's replicas "
                "are engines of the dense family")
        spec = NAMED_SPECS[args.model_spec]
    spec_k, spec_method = 0, "ngram"
    if args.speculate is not None:
        try:
            spec_k, spec_method = _parse_speculate(args.speculate)
        except ValueError as e:
            raise SystemExit(f"--speculate: {e}")
    # The engine has no optimizer boundary, so a precision POLICY here
    # degenerates to its compute dtype ("bf16" -> bfloat16 matmuls,
    # "fp32" -> strict fp32 even on TPU); kv_dtype is the serve-side
    # storage knob the policy does not own.
    prec = _resolve_precision(args)
    serve_dtype = ("bfloat16" if prec == "bf16"
                   else None if prec == "fp32" else _resolve_dtype(args))
    cfg = ServeConfig(
        spec=spec,
        slots=args.slots,
        capacity=args.capacity,
        tensor_parallel=args.tensor_parallel,
        temperature=args.temperature,
        top_k=args.top_k,
        seed=args.seed,
        compute_dtype=serve_dtype,
        kv_dtype=args.kv_dtype,
        prefix_slots=args.prefix_cache,
        prefill_chunk=args.prefill_chunk,
        prefill_budget=args.prefill_budget,
        page_size=args.page_size,
        num_pages=args.num_pages,
        speculate_k=spec_k,
        speculate_method=spec_method,
    )
    if args.top_k and args.temperature <= 0:
        # Same flag hygiene as the variant-group rejects above: greedy
        # decode never reaches the top-k branch, so the flag would be
        # silently ignored.
        raise SystemExit(
            "--top-k requires --temperature > 0 (greedy decode ignores it)"
        )
    if args.traffic is not None and args.replicas is None:
        raise SystemExit("--traffic requires --replicas (the router path)")
    if args.slo is not None and args.replicas is None:
        raise SystemExit("--slo requires --replicas (the router path)")
    if args.autoscale is not None and args.replicas is None:
        raise SystemExit(
            "--autoscale requires --replicas (the fleet controller "
            "drives the router)"
        )
    if args.max_replicas is not None and args.autoscale is None:
        raise SystemExit(
            "--max-replicas requires --autoscale (it caps the fleet "
            "the controller may grow; pass --autoscale '' for defaults)"
        )
    # Disagg/speculation flag hygiene BOTH WAYS (ISSUE 15): each
    # rejection names the offending combination — bare single-engine
    # serve and contiguous engines reject the flags loudly instead of
    # silently serving colocated/plain.
    if args.roles is not None:
        if args.replicas is None:
            raise SystemExit(
                f"--roles {args.roles} requires --replicas (roles "
                "split the ROUTER's fleet by phase; bare single-engine "
                "serve has no fleet to split)"
            )
        if args.page_size <= 0:
            raise SystemExit(
                f"--roles {args.roles} requires --page-size > 0 (the "
                "prefill->decode hand-off moves KV pages; the "
                "contiguous slot-ring layout has none)"
            )
    if args.speculate is not None:
        if args.replicas is None:
            raise SystemExit(
                f"--speculate {args.speculate} requires --replicas "
                "(speculative serving runs behind the router; bare "
                "single-engine serve rejects the flag)"
            )
        if args.page_size <= 0:
            raise SystemExit(
                f"--speculate {args.speculate} requires --page-size > 0 "
                "(draft lanes verify through block-table ALIASES of "
                "the speculating slot's pages; the contiguous layout "
                "has no pages to alias)"
            )
    if args.replicas is not None:
        return _run_serve_router(args, cfg)
    if args.max_new_tokens < 1:
        raise SystemExit(
            f"--max-new-tokens must be >= 1, got {args.max_new_tokens}"
        )
    if args.prompt_max + args.max_new_tokens > args.capacity:
        raise SystemExit(
            f"serve config error: --prompt-max {args.prompt_max} + "
            f"--max-new-tokens {args.max_new_tokens} exceeds --capacity "
            f"{args.capacity}"
        )
    # Validate the checkpoint path BEFORE building the engine (a typo'd
    # path must not cost a full param init + placement), and hand the
    # loaded host tree straight to the constructor (no throwaway random
    # init is ever placed).
    ckpt = checkpoint_file(args.checkpoint_dir)
    if ckpt is not None:
        import os

        if not os.path.exists(ckpt):
            raise SystemExit(f"no checkpoint at {ckpt}")
    try:
        Engine = engine_cls(spec)
        engine = (Engine.from_checkpoint(cfg, ckpt)
                  if ckpt is not None else Engine(cfg))
    except (ValueError, KeyError) as e:
        raise SystemExit(f"serve config error: {e}")
    if ckpt is not None:
        print(f"[ddl_tpu] serving params from {ckpt} (params-only load)")
    try:
        prompts = synthesize_prompts(
            num=args.num_prompts, min_len=args.prompt_min,
            max_len=args.prompt_max, vocab=spec.vocab, seed=args.seed,
        )
    except ValueError as e:
        raise SystemExit(f"serve config error: {e}")
    requests = [
        Request(id=i, prompt=pr, max_new_tokens=args.max_new_tokens)
        for i, pr in enumerate(prompts)
    ]
    registry, writer, _ = _build_obs(
        args, config=cfg, mesh=engine.mesh, make_tracer=False
    )
    monitor = _make_slo_monitor(args, registry)
    detector = _make_anomaly(args, registry)
    injector = _make_injector(args, "serve")
    if injector is not None and injector.spec.kind == "replica_crash":
        # The bare scheduler never consults crashes_replica — silently
        # dropping the fault would fake a passing chaos run.
        raise SystemExit(
            "--inject-fault replica_crash needs --replicas and "
            "--autoscale (only the fleet controller delivers the crash)"
        )
    try:
        scheduler = Scheduler(
            engine, registry=registry, metrics_writer=writer,
            ttft_deadline_s=args.ttft_deadline,
            deadline_s=args.request_deadline,
            shed_threshold=args.shed_threshold,
            injector=injector,
            slo_monitor=monitor,
            peak_flops=args.peak_flops,
            anomaly_detector=detector,
        )
    except ValueError as e:
        raise SystemExit(f"serve config error: {e}")
    from .obs.trace import trace_context

    # Exporter starts inside the guarded block (after the ctor, which
    # can SystemExit on config errors) so no exit path leaks the bound
    # port or its daemon thread — and before warmup, so a scraper sees
    # the compile ladder's xla_compiles_total live.
    exporter = None
    try:
        exporter = _start_exporter(args, registry)
        # Compile outside the reported run: the printed/JSON latency
        # percentiles and tok/s must measure serving, not jit (the
        # shared serve_bench/BASELINE.md methodology). Warmup also
        # suppresses telemetry, so the trace/metrics see only the
        # reported run.
        scheduler.warmup(requests)
        # --trace-dir: ONE context scopes both timelines — the host
        # request-lifecycle spans and the jax.profiler XLA timeline
        # land in the same directory for the same bracket (and the
        # profiler starts only now, after warmup's compilation).
        with trace_context(args.trace_dir) as tracer:
            scheduler.tracer = tracer
            if monitor is not None:
                # slo_alert events land in the run-scoped trace.
                monitor.tracer = tracer
            if detector is not None:
                # anomaly events too — the analyze CLI reads them back.
                detector.tracer = tracer
            done, stats = scheduler.run(requests)
    finally:
        if exporter is not None:
            exporter.close()
        if writer is not None:
            writer.close()
    slo_digest = _slo_report(monitor)
    anomaly_digest = _anomaly_report(detector)
    if registry is not None:
        gf = registry.get("goodput_fraction")
        if gf is not None and gf.value() is not None:
            # The live attribution digest (ISSUE 11): where the run's
            # observed wall time went, next to the throughput story.
            tis = registry.get("time_in_seconds")
            phases = " ".join(
                f"{ls['phase']}={tis.value(**ls):.2f}s"
                for ls in sorted(tis.label_sets(),
                                 key=lambda d: -tis.value(**d))
                if tis.value(**ls) > 0
            ) if tis is not None else ""
            print(f"goodput: {gf.value():.1%} ({phases})")
    for i in sorted(done):
        c = done[i]
        tag = "" if c.status == "ok" else f" [{c.status}]"
        print(f"request {i}: prompt {c.prompt_len} tokens -> "
              f"{len(c.tokens)} generated {c.tokens[:8]}"
              f"{'...' if len(c.tokens) > 8 else ''}{tag}")
    lat = stats.latency
    print(f"prefill {stats.prefill_tokens_per_s:.0f} tok/s | decode "
          f"{stats.decode_tokens_per_s_per_slot:.1f} tok/s/slot "
          f"({stats.slots} slots) | per-token latency p50 "
          f"{lat.p50_ms:.1f}ms p95 {lat.p95_ms:.1f}ms p99 {lat.p99_ms:.1f}ms")
    print(f"ttft p50 {stats.ttft.p50_ms:.1f}ms p95 {stats.ttft.p95_ms:.1f}ms"
          f" | itl p95 {stats.itl.p95_ms:.1f}ms")
    if args.prefix_cache:
        print(f"prefix cache: {stats.prefix_hits}/{stats.prefix_lookups} "
              f"hits ({stats.prefix_hit_rate:.0%}), "
              f"{stats.prefill_tokens_saved} prefill tokens saved")
    if args.page_size:
        print(f"paged pool: {engine.num_pages} pages x {args.page_size} "
              f"rows, {engine.pages.free} free at exit, "
              f"{engine.page_copies} CoW tail-page copies")
    if args.json:
        print(json.dumps({
            "variant": "serve",
            "config": dataclasses.asdict(cfg),
            "num_prompts": args.num_prompts,
            "max_new_tokens": args.max_new_tokens,
            "completions": {
                str(i): {"prompt_len": done[i].prompt_len,
                         "tokens": done[i].tokens,
                         "status": done[i].status}
                for i in sorted(done)
            },
            # Per-class completion/status tallies (ISSUE 8 satellite):
            # the single-engine path serves one "default" class, the
            # --replicas router path real ones — chaos chains assert
            # shedding hit the right class from this either way.
            "per_class": _class_tallies(
                done, {r.id: r.traffic_class for r in requests}
            ),
            "slo_rules": slo_digest,
            "anomaly_rules": anomaly_digest,
            "goodput": (scheduler.goodput.summary()
                        if scheduler.goodput is not None else None),
            "prefill_tokens_per_s": stats.prefill_tokens_per_s,
            "decode_tokens_per_s_per_slot":
                stats.decode_tokens_per_s_per_slot,
            "decode_steps": stats.decode_steps,
            "latency_ms": {"p50": lat.p50_ms, "p95": lat.p95_ms,
                           "p99": lat.p99_ms},
            "ttft_ms": {"p50": stats.ttft.p50_ms, "p95": stats.ttft.p95_ms},
            "itl_ms": {"p50": stats.itl.p50_ms, "p95": stats.itl.p95_ms,
                       "p99": stats.itl.p99_ms},
            "prefix_lookups": stats.prefix_lookups,
            "prefix_hits": stats.prefix_hits,
            "prefill_tokens_saved": stats.prefill_tokens_saved,
            "kv_page_copies": engine.page_copies if args.page_size else 0,
            "kv_pages_free": engine.pages.free if args.page_size else 0,
        }))
    return 0


def main(argv: list[str] | None = None) -> int:
    from .utils import compile_cache

    compile_cache.enable()
    args = build_parser().parse_args(argv)
    if args.metrics_interval is not None:
        if args.metrics_interval < 1:
            raise SystemExit(
                f"--metrics-interval must be >= 1, got "
                f"{args.metrics_interval}"
            )
        if args.metrics_out is None:
            # Same loud-fail hygiene as the variant flag groups: an
            # interval without a sink would be silently ignored. The
            # parser default is None (not 10) precisely so an EXPLICIT
            # `--metrics-interval 10` cannot slip past this check.
            raise SystemExit("--metrics-interval requires --metrics-out")
    else:
        args.metrics_interval = 10
    if args.max_bad_steps is not None:
        if args.max_bad_steps < 1:
            raise SystemExit(
                f"--max-bad-steps must be >= 1, got {args.max_bad_steps}"
            )
        if args.variant not in ("single", "lm"):
            raise SystemExit(
                "--max-bad-steps applies to the single/lm variants (the "
                "guarded trainers)"
            )
        if not args.checkpoint_dir:
            # Rollback needs a checkpoint to roll back TO; failing at
            # the trip (mid-run) would waste the whole run.
            raise SystemExit(
                "--max-bad-steps rollback requires --checkpoint-dir"
            )
    if args.inject_fault and args.variant not in ("single", "lm", "serve"):
        raise SystemExit(
            "--inject-fault applies to the single/lm/serve variants"
        )
    if args.anomaly_rules and args.variant not in ("single", "lm", "serve"):
        # The sync/async span loops predate the per-tick obs feed —
        # the flag would be silently ignored there (same loud-fail
        # hygiene as the variant groups).
        raise SystemExit(
            "--anomaly-rules applies to the single/lm/serve variants"
        )
    if args.platform:
        import jax

        if args.platform != "cpu":
            jax.config.update("jax_platforms", args.platform)
        else:
            if args.multihost and args.num_processes:
                # Multi-process CPU world: the GLOBAL device count must be
                # the full mesh (num_workers, times dp and tp for the lm
                # 3-D topologies), spread evenly over the processes — a
                # blanket 8 per process would put the whole mesh on
                # process 0 and leave the others owning no rows
                # (make_mesh rejects that).
                # Mirror _run_lm's num_workers defaulting (1 under
                # pipeline parallelism — no sequence axis) so this
                # world-size computation and the mesh it later builds
                # can never disagree.
                total = ((args.num_workers
                          or (1 if args.pipeline_parallel > 1
                              else args.num_processes))
                         * args.data_parallel * args.tensor_parallel
                         * args.pipeline_parallel)
                if total % args.num_processes:
                    raise SystemExit(
                        f"total devices {total} (num-workers x "
                        f"data-parallel x tensor-parallel x "
                        f"pipeline-parallel) is not divisible by "
                        f"--num-processes {args.num_processes}"
                    )
                n_local = total // args.num_processes
            else:
                # lm 2-D/3-D topologies need num_workers * data_parallel
                # * tensor_parallel devices (both default to 1 elsewhere).
                # Pipeline topologies default num_workers to 1 (no
                # sequence axis) — mirror _run_lm's defaulting here so
                # the virtual device count matches the mesh it builds.
                default_w = 1 if args.pipeline_parallel > 1 else 8
                n_local = max(
                    (args.num_workers or default_w) * args.data_parallel
                    * args.tensor_parallel * args.pipeline_parallel,
                    8,
                )
            from .parallel.mesh import virtual_cpu_mesh

            try:
                virtual_cpu_mesh(n_local)
            except RuntimeError as e:
                raise SystemExit(f"--platform cpu: {e}")
    if args.multihost:
        # Before any backend use: joining the world after the local backend
        # initializes would freeze a single-process device view.
        import jax

        from .parallel import multihost

        multihost.initialize(
            args.coordinator, args.num_processes, args.process_id
        )
        print(f"[ddl_tpu] multihost: process {jax.process_index()}/"
              f"{jax.process_count()}, {len(jax.devices())} global devices")
    if args.variant == "sim":
        return _run_sim(args)
    if args.variant == "serve":
        return _run_serve(args)
    if args.variant == "lm":
        return _run_lm(args)
    # MNIST variants get the same loud-fail hygiene for the serve-only
    # flags (a typo'd `sync --slots 8` must not silently train).
    _reject_foreign_flags(args, args.variant,
                          _SERVE_ONLY_DESTS + _SIM_ONLY_DESTS)
    from .data import load_mnist

    dataset = load_mnist(
        path=args.data,
        synthetic_train=args.synthetic_train,
        synthetic_test=args.synthetic_test,
    )
    cfg = config_from_args(args)
    if args.variant != "single":
        _ensure_devices(cfg.num_workers)

    if args.variant == "single":
        from .train.trainer import SingleChipTrainer

        trainer = SingleChipTrainer(cfg, dataset)
    elif args.variant.startswith("sync"):
        from .strategies.sync import SyncTrainer

        trainer = SyncTrainer(cfg, dataset)
    else:
        from .strategies.async_ps import AsyncTrainer

        trainer = AsyncTrainer(cfg, dataset)

    if args.resume and not args.checkpoint_dir:
        raise SystemExit("--resume requires --checkpoint-dir")
    from .parallel.mesh import AcceleratorTimeout

    registry, writer, tracer = _build_obs(
        args, config=cfg, mesh=getattr(trainer, "mesh", None)
    )
    exporter = _start_exporter(args, registry)
    obs_kwargs = {}
    detector = None
    run_span = contextlib.nullcontext()
    if args.variant == "single":
        # In-graph health + span tracing ride the single-chip trainer
        # (train.trainer); the sync/async strategies report end-of-run
        # summaries into the registry below (their span loops predate
        # the obs layer — README Observability).
        detector = _make_anomaly(args, registry, tracer)
        obs_kwargs = dict(
            metrics=registry, metrics_interval=args.metrics_interval,
            metrics_writer=writer, tracer=tracer,
            max_bad_steps=args.max_bad_steps or 0,
            fault_injector=_make_injector(args, "single"),
            peak_flops=args.peak_flops,
            ici_bw=args.ici_bw,
            anomaly_detector=detector,
        )
    elif tracer is not None:
        # sync/async: the trainers take no tracer, but --trace-dir must
        # still deliver the promised host_trace_p*.jsonl — one coarse
        # run-level span wraps the whole training call.
        run_span = tracer.span("train/run", variant=args.variant)
    term = _install_sigterm_flag(bool(args.checkpoint_dir))
    try:
        with run_span:
            result = trainer.train(
                checkpoint_dir=args.checkpoint_dir,
                checkpoint_every=args.checkpoint_every,
                resume=args.resume,
                profile_dir=args.profile or args.trace_dir,
                should_stop=lambda: term["flag"],
                dispatch_timeout=args.dispatch_timeout,
                **obs_kwargs,
            )
            if registry is not None:
                registry.gauge("train_final_accuracy").set(
                    result.final_accuracy
                )
                registry.gauge("train_run_images_per_sec").set(
                    result.images_per_sec
                )
                if args.variant != "single" and result.train_time_s > 0:
                    # sync/async report summary-level telemetry only
                    # (their span loops predate the obs layer): one
                    # end-of-run MFU from the analytic per-image FLOPs
                    # and the run-average throughput (obs.cost).
                    import jax

                    from .obs import cost as _cost

                    registry.gauge("train_mfu").set(_cost.mfu(
                        _cost.cnn_train_step_flops(
                            1, cfg.conv_channels, cfg.fc_sizes
                        ) * result.images_per_sec * result.train_time_s,
                        result.train_time_s,
                        max(1, cfg.num_workers),
                        _cost.peak_flops_per_device(
                            jax.devices()[0], args.peak_flops,
                            precision=cfg.policy().mfu_kind,
                        ),
                    ))
    except AcceleratorTimeout as e:
        return _fatal_timeout(e)
    finally:
        # Any exit path with a live interpreter still forces a final
        # snapshot (the timeout path os._exits by contract).
        if exporter is not None:
            exporter.close()
        if tracer is not None:
            tracer.close()
        if writer is not None:
            writer.close()
    anomaly_digest = _anomaly_report(detector)
    print(f"training time: {result.train_time_s:.2f}s "
          f"({result.images_per_sec:.0f} images/s, "
          f"compile {result.compile_time_s:.1f}s excluded)")
    if result.step_stats and result.step_stats.steps:
        print(f"step stats (per dispatched span): {result.step_stats.line()}")
    if args.json:
        print(json.dumps({
            "variant": args.variant,
            "anomaly_rules": anomaly_digest,
            "config": dataclasses.asdict(cfg),
            "final_accuracy": result.final_accuracy,
            # (epoch, batch/round, accuracy) per eval point — the
            # machine-readable form of the reference's accuracy prints
            # (mnist_sync/worker.py:71-72).
            "history": [[e, b, round(a, 6)] for e, b, a in result.history],
            # Async only: per-eval accuracies of every worker's stale
            # replica (the reference's W per-worker accuracy streams,
            # mnist_async/worker.py:71-75). null for sync/single.
            "worker_history": (
                [[e, b, [round(a, 6) for a in accs]]
                 for e, b, accs in result.worker_history]
                if result.worker_history is not None else None
            ),
            "train_time_s": result.train_time_s,
            "images_per_sec": result.images_per_sec,
            "compile_time_s": result.compile_time_s,
            "step_stats": dataclasses.asdict(result.step_stats)
                          if result.step_stats else None,
            "resumed_from_step": result.resumed_from_step,
            "preempted": result.preempted,
            "skipped_steps": result.skipped_steps,
            "rollbacks": result.rollbacks,
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
