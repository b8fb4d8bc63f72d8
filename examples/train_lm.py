"""End-to-end driver: train a ~25M-parameter LM with SAFL for a few hundred
rounds on synthetic federated data, with cosine LR, checkpointing, and an
uncompressed FedOPT reference (deliverable b).

    PYTHONPATH=src python examples/train_lm.py [--rounds 200] [--big]

--big uses a ~100M model (BERT-scale, the paper's language setup).
--participation-frac 0.4 samples a 2-of-5 cohort per round (repro.fed);
--async-buffer 2 runs the FedBuff-style staleness buffer with client delays
up to 2 rounds.  Both ride the scanned driver's hooks and keep the
trajectory resumable: each chunk checkpoint stores the (t, key) cursor, and
because every per-round stream (data, cohorts, delays, sketch operators) is
a pure function of the absolute round index, a restart from the cursor
replays the identical trajectory.

Robustness (DESIGN.md §10): --faults 0.15 injects deterministic client
faults (dropout-after-compute / NaN payloads / Byzantine scaling, rate/3
each); --sentinel turns on the sketch-space payload sentinels that reject
the corrupted uplinks; --max-retries 3 wraps the run in the
checkpoint-rollback supervisor, which rolls a diverged span back to the
last good (t, key) cursor and re-runs it under a rekeyed fault stream,
printing the recovery log at exit.

Observability (DESIGN.md §11): --telemetry turns on the in-graph probes
(delta/update norms, desketch residual, moment norms, effective cohort)
and streams per-chunk JSONL metric shards + a run manifest into
--telemetry-out (default <ckpt>_obs), then prints a compact end-of-run
summary.  Render with ``python tools/obs_report.py <dir>``.  NOTE the
probes are extra scan outputs, so a --telemetry trajectory is its own
program family -- bit-comparable to other --telemetry runs, not to the
probe-free default (the fusion caveat DESIGN §11 documents).

Payload codec (DESIGN.md §13): --codec int8 / --codec 1bit quantizes the
packed sketch uplink with stochastic rounding + sketch-space error
feedback, and switches uplink_bits to the MEASURED encoded size
(per-row scale + mantissa bits, billed to the clients that actually
transmitted).  The EF memory rides in the scanned optimizer state, so
--resume round-trips it like any other carry leaf.
"""
import argparse
import functools
import os

import jax
import jax.numpy as jnp

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.core.adaptive import AdaConfig
from repro.core.packed import make_packing_plan
from repro.core.safl import SAFLConfig, fedopt_round, init_safl, safl_round
from repro.core.sketch import SketchConfig
from repro.data import BigramLMData, LMDataConfig
from repro.fed import AsyncConfig, CodecConfig, FaultConfig, \
    SentinelConfig, UniformParticipation, init_async_state, \
    init_codec_state, make_async_round
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.driver import run_scan
from repro.launch.supervisor import SupervisorConfig, format_recovery_log, \
    run_supervised
from repro.models import ModelConfig, init_params, loss_fn
from repro.obs import ShardWriter, Telemetry, format_summary, write_manifest
from repro.optim import cosine

ap = argparse.ArgumentParser()
ap.add_argument("--rounds", type=int, default=200)
ap.add_argument("--big", action="store_true")
ap.add_argument("--ratio", type=float, default=0.02)
ap.add_argument("--ckpt", default="/tmp/safl_lm")
ap.add_argument("--fedopt", action="store_true", help="run the uncompressed"
                " reference instead of SAFL")
ap.add_argument("--participation-frac", type=float, default=1.0,
                help="fraction of clients sampled per round (repro.fed "
                "uniform-without-replacement cohorts; 1.0 = all)")
ap.add_argument("--async-buffer", type=int, default=0, metavar="MAX_DELAY",
                help="run the FedBuff-style staleness buffer with client "
                "delays up to MAX_DELAY rounds (0 = synchronous)")
ap.add_argument("--faults", type=float, default=0.0, metavar="RATE",
                help="inject deterministic client faults at this total "
                "rate, split RATE/3 each across dropout-after-compute, "
                "NaN-corrupted payloads, and 1e3-scaled Byzantine payloads "
                "(repro.fed.faults; 0 = fault-free)")
ap.add_argument("--sentinel", action="store_true",
                help="enable the sketch-space payload sentinels: per-"
                "client finite checks + norm-outlier rejection folded "
                "into the aggregation mask (repro.fed.robust)")
ap.add_argument("--max-retries", type=int, default=0, metavar="N",
                help="wrap the run in the checkpoint-rollback supervisor "
                "with up to N rekeyed retries of a diverged span "
                "(launch/supervisor.py; 0 = unsupervised)")
ap.add_argument("--telemetry", action="store_true",
                help="enable the in-graph telemetry probes and stream "
                "per-chunk JSONL metric shards + a run manifest "
                "(repro.obs, DESIGN.md §11)")
ap.add_argument("--telemetry-out", default=None, metavar="PATH",
                help="run directory for the telemetry shards/manifest "
                "(default: <--ckpt>_obs)")
ap.add_argument("--codec", choices=["int8", "1bit"], default=None,
                help="quantize the packed sketch uplink with the payload "
                "codec (stochastic rounding + sketch-space error feedback, "
                "repro.fed.codec, DESIGN.md §13); uplink_bits becomes the "
                "measured encoded size")
ap.add_argument("--resume", action="store_true",
                help="restart from --ckpt's (t, key) cursor and resume the "
                "EXACT trajectory (pass the same model/algorithm flags): "
                "every per-round stream is a pure function of the absolute "
                "round index, so the resumed run is bit-identical to an "
                "uninterrupted one (tests/test_resume.py)")
args = ap.parse_args()
enable_compile_cache()

if args.big:  # ~100M (paper's BERT scale)
    model = ModelConfig(name="lm100m", arch_type="dense", num_layers=12,
                        d_model=768, num_heads=12, num_kv_heads=12,
                        d_ff=3072, vocab_size=8192)
else:         # ~25M -- trains a few hundred rounds in CPU-minutes
    model = ModelConfig(name="lm25m", arch_type="dense", num_layers=6,
                        d_model=384, num_heads=6, num_kv_heads=6,
                        d_ff=1536, vocab_size=4096)

safl = SAFLConfig(
    sketch=SketchConfig(kind="countsketch", ratio=args.ratio, min_b=64),
    server=AdaConfig(name="amsgrad", lr=0.01),
    client_lr=0.5, local_steps=2)

data = BigramLMData(LMDataConfig(vocab_size=model.vocab_size, seq_len=64,
                                 num_clients=5, heterogeneity=0.3,
                                 alpha=0.02))
params = init_params(model, jax.random.key(0))
opt = init_safl(safl, params)
loss = lambda p, b: loss_fn(model, p, b)
sampler = data.device_sampler(batch_per_client=8, local_steps=2)
sched = cosine(args.rounds, warmup=10)

# PackingPlan built once outside the trace; the fused multi-round driver
# (launch/driver.py) scans whole chunks on device with donated carries and
# checkpoints at chunk boundaries.  The cosine server LR rides in through
# kwargs_fn as a function of the scanned round index.
if args.fedopt and args.async_buffer > 0:
    ap.error("--async-buffer is SAFL-only; drop --fedopt to run the "
             "staleness buffer")
if args.fedopt and (args.faults > 0 or args.sentinel):
    ap.error("--faults/--sentinel act on the packed sketch uplink; the "
             "uncompressed FedOPT reference has no sketch payload")
if args.fedopt and args.codec:
    ap.error("--codec quantizes the packed sketch uplink; the uncompressed "
             "FedOPT reference has no sketch payload")
if args.codec and args.telemetry:
    ap.error("--telemetry probes read the bare server opt state; under the "
             "codec's error feedback the round state is the wrapped "
             "{'opt','ef'} dict -- run one or the other")

sentinel = SentinelConfig(norm_mult=10.0) if args.sentinel else None
codec = None
if args.codec:
    codec = CodecConfig(bits=8 if args.codec == "int8" else 1)
plan = make_packing_plan(safl.sketch, params)
async_cfg = None
if args.fedopt:
    round_fn = functools.partial(fedopt_round, safl, loss)
elif args.async_buffer > 0:
    async_cfg = AsyncConfig(max_delay=args.async_buffer, delay="uniform")
    round_fn = make_async_round(safl, loss, async_cfg, plan, codec=codec)
    opt = init_async_state(safl, async_cfg, params, plan,
                           data.cfg.num_clients, codec=codec)
else:
    round_fn = functools.partial(safl_round, safl, loss, plan=plan)
    if codec is not None:
        # static config, binds like plan=/sentinel= (DESIGN.md §13).  The
        # error-feedback memory becomes an extra optimizer-state leaf so the
        # scan carries it and --resume round-trips it.
        round_fn = functools.partial(round_fn, codec=codec)
        if codec.error_feedback:
            opt = {"opt": opt,
                   "ef": init_codec_state(codec, data.cfg.num_clients,
                                          plan.b_total)}
if sentinel is not None:
    # static config: binds like plan=, not a traced kwarg (DESIGN.md §10)
    round_fn = functools.partial(round_fn, sentinel=sentinel)

telemetry = stream = None
if args.telemetry:
    telemetry = Telemetry()
    if args.async_buffer == 0:
        # static config, binds like plan=/sentinel=.  (The async round
        # closure owns its multi-generation aggregation and takes no probe
        # config; its arrival_weight/counter metrics still stream.)
        round_fn = functools.partial(round_fn, telemetry=telemetry)
    obs_dir = args.telemetry_out or (args.ckpt + "_obs")
    stream = ShardWriter(obs_dir)
    write_manifest(obs_dir, run="train_lm", sketch=safl.sketch,
                   config={k: v for k, v in vars(args).items()})
    print("telemetry: streaming metric shards to", obs_dir)

faults = None
if args.faults > 0:
    r = args.faults / 3.0
    faults = FaultConfig(num_clients=data.cfg.num_clients, drop_rate=r,
                         nan_rate=r, byzantine_rate=r)
    print(f"fault injection: total rate {args.faults} "
          f"(drop/NaN/Byzantine {r:.3f} each)"
          + ("" if args.sentinel else " -- UNGUARDED, pass --sentinel"))

participation = None
if args.participation_frac < 1.0:
    participation = UniformParticipation(data.cfg.num_clients,
                                         frac=args.participation_frac)
    print(f"partial participation: {participation.cohort_size}"
          f"/{data.cfg.num_clients} clients per round")
if async_cfg is not None:
    print(f"async staleness buffer: max delay {async_cfg.max_delay} rounds")
if codec is not None:
    print(f"payload codec: {args.codec} "
          f"({codec.payload_bits(plan.b_total)} measured bits/client/round "
          f"vs {32 * plan.b_total} float32)")

n = sum(int(jnp.size(p)) for p in jax.tree.leaves(params))
print(f"{'FedOPT' if args.fedopt else 'SAFL'} on {n/1e6:.1f}M params, "
      f"sketch ratio {args.ratio}")

key = jax.random.key(0)

start_round = 0
if args.resume:
    # the `like` tree fixes structure/dtypes, so a checkpoint from different
    # flags (other model / optimizer / async state) fails loudly here
    like = {"params": params, "opt": opt,
            "cursor": {"t": jnp.asarray(0), "key": jax.random.key_data(key)}}
    state, _step = restore_checkpoint(args.ckpt, like)
    params, opt = state["params"], state["opt"]
    key = jax.random.wrap_key_data(state["cursor"]["key"])
    start_round = int(state["cursor"]["t"])
    print(f"resuming from {args.ckpt}.npz at round {start_round}")


def on_chunk(t_done, p, o, hist):
    print(f"round {t_done - 1:4d}  loss {hist['loss'][-1]:.4f}")
    if args.max_retries == 0 and t_done < args.rounds:
        # resumable cursor: (t, key) pins where the trajectory restarts --
        # data, cohort masks, delays and sketch operators are all pure
        # functions of the absolute round index under this key.  (The
        # supervisor owns checkpointing when it is on: it must record the
        # REKEYED cursor of a retried span, not this run key.)
        save_checkpoint(args.ckpt, {"params": p, "opt": o,
                                    "cursor": {"t": jnp.asarray(t_done),
                                               "key": jax.random.key_data(key)}},
                        step=t_done)


if args.max_retries > 0:
    def launch(p, o, *, key, start_round, on_chunk):
        return run_scan(
            round_fn, sampler, p, o, rounds=args.rounds, key=key,
            chunk_size=100, kwargs_fn=lambda t: {"lr_scale": sched(t)},
            on_chunk=on_chunk, participation=participation,
            buffer=async_cfg is not None, faults=faults,
            start_round=start_round, stream=stream)

    params, opt, hist, recovery = run_supervised(
        launch, params, opt, rounds=args.rounds, key=key,
        config=SupervisorConfig(max_retries=args.max_retries),
        on_chunk=on_chunk, ckpt_path=args.ckpt, start_round=start_round,
        stream=stream)
    print(format_recovery_log(recovery))
else:
    params, opt, hist = run_scan(
        round_fn, sampler, params, opt, rounds=args.rounds, key=key,
        chunk_size=100, kwargs_fn=lambda t: {"lr_scale": sched(t)},
        on_chunk=on_chunk, participation=participation,
        buffer=async_cfg is not None, faults=faults,
        start_round=start_round, stream=stream)
    save_checkpoint(args.ckpt, {"params": params, "opt": opt,
                                "cursor": {"t": jnp.asarray(args.rounds),
                                           "key": jax.random.key_data(key)}},
                    step=args.rounds)
if stream is not None:
    print(format_summary(stream.summary()))
print("checkpoint saved to", args.ckpt + ".npz")
