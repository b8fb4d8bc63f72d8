#!/usr/bin/env python3
"""Chip smoke: the SAFL trainer's main path on a TPU, at bert-100m width.

    python chip_smoke.py          # one chip: training phase + kernel phase
    python chip_smoke.py --mesh   # four chips: cross-silo mesh phase only

Default run, on one chip:

1. train -- the path of ``examples/train_lm.py``: ``make_packing_plan``, then
   ``safl_round`` (balanced count-sketch at ratio 0.02, AMSGrad server, two
   local steps), then the scanned driver ``run_scan``.  The model is
   ``configs/bert_100m.py`` at its published widths (12 layers, d_model 768,
   vocab 30522, f32) with random weights from ``--seed``.  Five clients of
   bigram data, 4 rounds in 2 chunks of 2, so the second chunk reuses the
   compiled program.
2. kernels -- the Pallas kernels through ``repro.kernels.ops``, compiled for
   the chip (not interpreted), each compared with ``repro.kernels.ref``.

``--mesh`` runs only the four-chip path: ``run_mesh_scan`` on a
(pod=2, data=1, model=2) mesh, topology cross_silo (2 clients, each holding
the model sharded two ways), against the per-round ``run_mesh_host_loop``.

The script refuses any platform but a TPU.  Every phase must pass; any
exception ends the run non-zero.  Lines before the last are informational,
not a benchmark.  The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

DATA_VOCAB = 4096      # bigram table (G, V, V) f32 must fit next to the model
SEQ_LEN = 512
NUM_CLIENTS = 5
BATCH_PER_CLIENT = 8
LOCAL_STEPS = 2
# examples/train_lm.py's default server-LR schedule: cosine over 200 rounds
# with 10 warm-up rounds; the smoke runs its first rounds
SCHEDULE_ROUNDS, WARMUP_ROUNDS = 200, 10

# stated tolerances: max |kernel - reference| / max(1, max |reference|)
TOL_COUNTSKETCH = 1e-5
TOL_FWHT = 1e-5
TOL_GAUSSIAN = 1e-3    # the same bound tests/test_kernels.py holds them to
# max |scanned - per-round| loss, relative to the loss
TOL_MESH_LOSS = 1e-4


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def _check(ok, msg) -> None:
    if not ok:
        raise RuntimeError(msg)


def _check_platform(platform: str, count: int | None = None) -> None:
    found = jax.devices()[0].platform
    if found != platform:
        raise RuntimeError(f"expected platform {platform!r}, found {found!r}")
    if count is not None and len(jax.devices()) < count:
        raise RuntimeError(f"needs {count} devices, found {len(jax.devices())}")


def safl_config():
    from repro.core.adaptive import AdaConfig
    from repro.core.safl import SAFLConfig
    from repro.core.sketch import SketchConfig
    return SAFLConfig(
        sketch=SketchConfig(kind="countsketch", ratio=0.02, min_b=64),
        server=AdaConfig(name="amsgrad", lr=0.01),
        client_lr=0.5, local_steps=LOCAL_STEPS)


def bigram_data(num_clients: int, data_vocab: int, seq_len: int, seed: int):
    from repro.data import BigramLMData, LMDataConfig
    return BigramLMData(LMDataConfig(
        vocab_size=data_vocab, seq_len=seq_len, num_clients=num_clients,
        heterogeneity=0.3, alpha=0.02, seed=seed))


def _all_finite(tree) -> bool:
    return all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(tree))


def _memory(device) -> dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_in_use",
                                      "largest_alloc_size", "bytes_limit")}


def run_train(model_cfg, platform: str, *, data_vocab: int = DATA_VOCAB,
              seq_len: int = SEQ_LEN, num_clients: int = NUM_CLIENTS,
              batch_per_client: int = BATCH_PER_CLIENT, rounds: int = 4,
              chunk: int = 2, seed: int = 0) -> dict:
    """The training phase: SAFL rounds through ``run_scan`` on one device."""
    from repro.core.packed import make_packing_plan
    from repro.core.safl import init_safl, safl_round
    from repro.launch.driver import run_scan
    from repro.models import init_params, loss_fn
    from repro.optim import cosine

    _check_platform(platform)
    _check(data_vocab <= model_cfg.vocab_size, "data vocabulary too large")
    if data_vocab < model_cfg.vocab_size:
        table = num_clients * data_vocab ** 2 * 4
        full = num_clients * model_cfg.vocab_size ** 2 * 4
        log(f"data vocabulary {data_vocab} (model keeps {model_cfg.vocab_size})"
            f": the bigram sampler holds a (G, V, V) f32 table on the device,"
            f" {full / 1e9:.1f} GB at the model's V, {table / 1e9:.3f} GB here")
    safl = safl_config()
    data = bigram_data(num_clients, data_vocab, seq_len, seed)
    sampler = data.device_sampler(batch_per_client=batch_per_client,
                                  local_steps=LOCAL_STEPS)
    params = init_params(model_cfg, jax.random.key(seed))
    opt = init_safl(safl, params)
    plan = make_packing_plan(safl.sketch, params)
    log(f"model {model_cfg.name}: d = {plan.d_total:,}, "
        f"b_total = {plan.b_total:,}, clients {num_clients}, "
        f"seq_len {seq_len}, batch/client {batch_per_client}, "
        f"materialized cohort")
    round_fn = functools.partial(safl_round, safl,
                                 lambda p, b: loss_fn(model_cfg, p, b),
                                 plan=plan)
    sched = cosine(SCHEDULE_ROUNDS, warmup=WARMUP_ROUNDS)
    marks = [time.perf_counter()]
    params, opt, hist = run_scan(
        round_fn, sampler, params, opt, rounds=rounds,
        key=jax.random.key(seed), chunk_size=chunk,
        kwargs_fn=lambda t: {"lr_scale": sched(t)},
        on_chunk=lambda *_: marks.append(time.perf_counter()))
    jax.block_until_ready(params)
    losses = [float(x) for x in hist["loss"]]
    first = marks[1] - marks[0]
    steady = (marks[-1] - marks[1]) / (rounds - chunk)
    log(f"compile + first chunk ({chunk} rounds): {first:.2f} s")
    log(f"later chunks: {steady:.3f} s per round")
    log(f"loss history: {losses}")
    _check(len(losses) == rounds and np.isfinite(losses).all(),
           f"non-finite loss history {losses}")
    _check(_all_finite(params), "non-finite final parameter")
    _check(_all_finite(opt), "non-finite server optimizer state")
    log(f"device memory: {_memory(jax.devices()[0])}")
    return {"d": plan.d_total, "b_total": plan.b_total, "losses": losses}


def _rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(1.0, np.max(np.abs(want))))


def run_kernels(platform: str, d: int, b_total: int, *, cs_scale: int = 64,
                fwht_shape: tuple = (64, 4096), gauss_n: int = 1 << 16,
                gauss_b: int = 1024, seed: int = 0) -> dict:
    """The kernel phase: each ``repro.kernels.ops`` kernel against its
    reference.  On a TPU the kernels must compile, not interpret."""
    from repro.kernels import ops, ref

    _check_platform(platform)
    _check(ops._interpret() == (platform != "tpu"), "wrong interpret mode")
    k1, k2, k3, k4 = jax.random.split(jax.random.key(seed), 4)
    errs = {}

    # count-sketch: the one-hot kernel costs O(n * b), so it runs at
    # d / cs_scale and b_total / cs_scale (same ratio, same G)
    n, b = d // cs_scale, max(1, b_total // cs_scale)
    x = jax.random.normal(k1, (NUM_CLIENTS, n), jnp.float32)
    h = jax.random.randint(k2, (n,), 0, b, jnp.int32)
    got = jax.block_until_ready(ops.countsketch_clients(x, h, b))
    t0 = time.perf_counter()
    got = jax.block_until_ready(ops.countsketch_clients(x, h, b))
    dt = time.perf_counter() - t0
    want = jax.jit(jax.vmap(lambda r: ref.countsketch_ref(r, h, b)))(x)
    errs["countsketch_clients"] = _rel_err(got, want)
    grid = lambda n_, b_: -(-b_ // 2048) * -(-n_ // 1024)
    log(f"countsketch_clients G={NUM_CLIENTS} n={n:,} b={b:,}: {dt:.4f} s; "
        f"at d={d:,}, b_total={b_total:,} the grid has "
        f"{grid(d, b_total) / grid(n, b):.0f}x the steps")

    xf = jax.random.normal(k3, fwht_shape, jnp.float32)
    got = ops.fwht_rows(xf)
    errs["fwht_rows"] = _rel_err(got, ref.fwht_ref(np.asarray(xf)))

    seed_arr = jnp.asarray(seed + 11, jnp.uint32)
    v = jax.random.normal(k4, (gauss_n,), jnp.float32)
    s = v[:gauss_b]
    errs["gaussian_sk"] = _rel_err(
        ops.gaussian_sk(seed_arr, v, gauss_b),
        ref.gaussian_sk_ref(seed + 11, np.asarray(v), gauss_b))
    errs["gaussian_desk"] = _rel_err(
        ops.gaussian_desk(seed_arr, s, gauss_n),
        ref.gaussian_desk_ref(seed + 11, np.asarray(s), gauss_n))

    tols = {"countsketch_clients": TOL_COUNTSKETCH, "fwht_rows": TOL_FWHT,
            "gaussian_sk": TOL_GAUSSIAN, "gaussian_desk": TOL_GAUSSIAN}
    for name, err in errs.items():
        log(f"kernel {name}: max rel error {err:.3e} (limit {tols[name]:g}, "
            f"{'interpreted' if ops._interpret() else 'compiled'})")
    bad = {k: e for k, e in errs.items() if not e <= tols[k]}
    _check(not bad, f"kernels off their references: {bad}")
    return errs


def run_mesh(model_cfg, platform: str, *, data_vocab: int = DATA_VOCAB,
             seq_len: int = SEQ_LEN, batch_per_client: int = BATCH_PER_CLIENT,
             rounds: int = 4, seed: int = 0) -> dict:
    """The four-chip phase: scanned cross-silo mesh rounds vs the per-round
    mesh loop, on a (pod=2, data=1, model=2) mesh of the first 4 devices."""
    from repro.core.safl import init_safl
    from repro.launch.mesh import _mesh
    from repro.launch.train import (make_safl_train_step, mesh_sampler,
                                    num_clients_of, run_mesh_host_loop,
                                    run_mesh_scan)
    from repro.models import init_params
    from repro.models.sharding import use_mesh

    _check_platform(platform, count=4)
    devices = jax.devices()[:4]
    mesh = _mesh((2, 1, 2), ("pod", "data", "model"), devices=devices)
    topology = "cross_silo"
    G = num_clients_of(mesh, topology)
    safl = safl_config()
    data = bigram_data(G, data_vocab, seq_len, seed)
    smp = mesh_sampler(mesh, data.device_sampler(batch_per_client,
                                                 LOCAL_STEPS), topology)
    key = jax.random.key(seed)

    def fresh():
        p = init_params(model_cfg, jax.random.key(seed))
        return p, init_safl(safl, p)

    log(f"mesh {dict(mesh.shape)} topology {topology}: {G} clients, "
        f"model {model_cfg.name}, {rounds} rounds")
    with use_mesh(mesh):
        step, _ = make_safl_train_step(model_cfg, safl, mesh, topology)
        t0 = time.perf_counter()
        _, _, h_loop = run_mesh_host_loop(step, smp, *fresh(), rounds=rounds,
                                          key=key)
        t1 = time.perf_counter()
        params, opt, h_scan = run_mesh_scan(
            model_cfg, safl, mesh, smp, *fresh(), rounds=rounds, key=key,
            topology=topology, chunk_size=2)
        jax.block_until_ready(params)
        t2 = time.perf_counter()
    loop, scan = (np.asarray(h_loop["loss"], np.float64),
                  np.asarray(h_scan["loss"], np.float64))
    diff = float(np.max(np.abs(scan - loop) / np.maximum(np.abs(loop), 1.0)))
    log(f"per-round loop losses: {loop.tolist()} ({t1 - t0:.2f} s incl. "
        f"compile)")
    log(f"scanned losses:        {scan.tolist()} ({t2 - t1:.2f} s incl. "
        f"compile)")
    log(f"max rel loss difference {diff:.3e} (limit {TOL_MESH_LOSS:g})")
    _check(np.isfinite(scan).all() and np.isfinite(loop).all(),
           "non-finite loss")
    _check(diff <= TOL_MESH_LOSS, "scanned and per-round losses differ")
    _check(_all_finite(params), "non-finite final parameter")

    per_device = {d.id: 0 for d in devices}
    n_split = 0
    for leaf in jax.tree.leaves(params):
        shard_devs = [s.device for s in leaf.addressable_shards]
        _check(len(set(shard_devs)) == len(shard_devs) > 1,
               f"leaf not spread over distinct devices: {leaf.sharding}")
        n_split += not leaf.sharding.is_fully_replicated
        for s in leaf.addressable_shards:
            per_device[s.device.id] += s.data.nbytes
    log(f"parameter bytes per device: {per_device}; "
        f"{n_split}/{len(jax.tree.leaves(params))} leaves split")
    _check(all(v > 0 for v in per_device.values()),
           f"a device holds no parameters: {per_device}")
    _check(n_split > 0, "every parameter leaf is replicated")
    for d in devices:
        log(f"device {d.id} memory: {_memory(d)}")
    return {"loop": loop.tolist(), "scan": scan.tolist(), "diff": diff,
            "per_device": per_device}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="run only the four-chip cross-silo mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    log(f"jax {jax.__version__}, device_kind {dev.device_kind!r}, "
        f"{len(jax.devices())} devices, compile cache {cache}")
    cfg = get_config("bert_100m")
    if args.mesh:
        run_mesh(cfg, "tpu", seed=args.seed)
    else:
        res = run_train(cfg, "tpu", seed=args.seed)
        run_kernels("tpu", res["d"], res["b_total"], seed=args.seed)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "src"))
    sys.exit(main())
