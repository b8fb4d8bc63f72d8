"""Device time of a round's stages, each run alone as the benchmark's own call.

The round is one fused program, so its trace cannot be split by stage.
Here each stage runs as a jitted call of its own at the cell's shapes, under
the profiler, and its device time is the sum of its program's events:

* ``client_stage``: the vmapped ``core.safl.client_delta`` over the cohort
  (K local SGD steps per client, forward and backward);
* ``sketch_stage``: ``core.packed.derive_round_params`` and
  ``sk_packed_clients`` on the cohort's (G, d) deltas;
* ``server_stage``: the cohort mean, ``desk_packed`` and
  ``core.adaptive.apply_update``.

A stage alone is not what it costs fused into the round; these numbers say
what each stage costs by itself.
"""

from __future__ import annotations

import functools
import glob

import jax
import jax.numpy as jnp

METRICS = ("client_ms", "sketch_ms", "sketch_roofline", "server_ms")


def measure(spec, prog, trace_dir: str) -> dict:
    """Device seconds of each stage, from one traced call each."""
    import repro.core.safl as safl_mod
    from repro.core.adaptive import apply_update
    from repro.core.packed import (derive_round_params, desk_packed,
                                   sk_packed_clients)
    from repro.models.model import loss_fn

    from bench import devtrace
    from bench.harness import profile_options

    safl, plan = prog.safl, prog.plan
    loss = functools.partial(loss_fn, prog.model_cfg)
    eta = jnp.float32(safl.client_lr)

    def client_stage(params, batch):
        return jax.vmap(lambda mb: safl_mod.client_delta(
            safl, loss, params, mb, eta))(batch)

    def sketch_stage(deltas, key):
        return sk_packed_clients(plan, derive_round_params(plan, key), deltas)

    def server_stage(params, state, payload, key):
        upd = desk_packed(plan, derive_round_params(plan, key),
                          jnp.mean(payload, axis=0))
        return apply_update(safl.server, state, params, upd)

    params = prog.init(prog.weights_key)
    state = jax.jit(functools.partial(safl_mod.init_safl, safl))(params)
    dstate = prog.sampler.init_state()
    batch = jax.jit(prog.sampler.sample)(dstate, jnp.int32(0))[1]
    key = jax.random.fold_in(prog.round_key, 0)
    calls = {}
    for fn in (client_stage, sketch_stage, server_stage):
        calls[fn.__name__] = jax.jit(fn)

    # compile all three before the trace starts
    client = calls["client_stage"].lower(params, batch).compile()
    d_abs = jax.eval_shape(client_stage, params, batch)[0]
    sketch = calls["sketch_stage"].lower(d_abs, key).compile()
    p_abs = jax.eval_shape(sketch_stage, d_abs, key)
    server = calls["server_stage"].lower(params, state, p_abs, key).compile()

    jax.profiler.start_trace(trace_dir, profiler_options=profile_options())
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        deltas, _ = jax.block_until_ready(client(params, batch))
        payload = jax.block_until_ready(sketch(deltas, key))
        del deltas
        jax.block_until_ready(server(params, state, payload, key))
    jax.profiler.stop_trace()
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    mods = devtrace.reduce(devtrace.load(files[-1]))["modules"]
    return {name: sum(v for k, v in mods.items() if name in k)
            for name in calls}
