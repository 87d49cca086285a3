"""Driver of the ``trainer-fit`` traffic: the swarm fit as a user runs it.

The timed path is the program's ``core.swarm.SwarmTrainer.fit``: one
compiled BSO-SL round (``core.engine.jit_swarm_round``) a call, with
the swarm state carried from call to call and the round's log (losses,
val accuracy, clusters) read back on the host after each. Each call is
``fit(state key, rounds=1)``, which continues the key chain exactly as
one ``fit(key, rounds=R)`` would.

The client model is the configuration's, from
``chipbench/models/<client_model>.py``, handed to the program through
its model-agnostic ``Model`` interface, as a clinic hands it its CNN.
The program runs at the JAX precision that gives the configuration's
``dtype`` (``PRECISION``).

Set-up (counted in ``setup_s``, from process start): the clinics from
the benchmark's own Table-I generator and the weights, both from
``--seed``; the trainer and its data layout; the first compile (or a
load from the compile cache); then the first ``check_calls`` calls,
whose outputs the correctness check compares.

Window: calls back to back until ``--seconds`` have passed; it ends
when the last call's log has been read. ``sim_round_ms`` is the
window's wall time over the rounds it completed. With ``--trace 1``
the window is instead ``trace_calls`` calls under the profiler.

After the window: the chip's peak memory is read, the program's state
is freed, and the plain reference (``chipbench/reference.py``) follows
the checked calls from the same weights, data and keys.
"""
from __future__ import annotations

import gc
import shutil
import sys
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import check, dr_data, reference, trace
from chipbench.run import BenchError

# the JAX matmul precision that computes a configuration's dtype on a TPU
# (JAX's default there is one bfloat16 pass)
PRECISION = {"float32": "highest"}


def seed_keys(seed: int):
    """(weight key, round key) from a whole number of up to 64 bits."""
    seed %= 2**64
    base = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)
    return jax.random.split(base)


def host_tree(tree) -> dict:
    return {k: np.asarray(v) for k, v in reference.tree_paths(tree)}


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class GcClock:
    """Collections of Python's cyclic garbage collector, and the seconds
    they took, while it is on."""

    def __init__(self):
        self.on, self.n, self.s, self._t0 = False, 0, 0.0, None
        gc.callbacks.append(self._event)

    def _event(self, phase, _info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self.on and self._t0 is not None:
            self.n += 1
            self.s += time.perf_counter() - self._t0


class CompileCounter:
    """Counts the backend compiles JAX reports while it is on."""

    def __init__(self):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_args, **_kw):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def precision(config: dict) -> str:
    if config["dtype"] not in PRECISION:
        raise BenchError(f"dtype {config['dtype']!r}: the harness runs "
                         f"{sorted(PRECISION)}")
    return PRECISION[config["dtype"]]


def adam(config: dict) -> reference.Adam:
    """The configuration's optimizer; the harness implements Adam only."""
    o = dict(config["optimizer"])
    if o.pop("name") != "adam":
        raise BenchError(f"optimizer {config['optimizer']['name']!r}: the "
                         "harness implements adam")
    return reference.Adam(**o)


def check_config(ctx):
    """The configuration states what the model file and the harness
    run, or the run stops."""
    c, m = ctx.config, ctx.model
    stated = {"stem": c["stem"], "fires": [tuple(f) for f in c["fires"]],
              "classes": c["classes"], "dropout": c["dropout"],
              "image_dtype": c["image_dtype"]}
    built = {"stem": m.STEM, "fires": [tuple(f) for f in m.FIRES],
             "classes": m.N_CLASSES, "dropout": 0.0, "image_dtype": "uint8"}
    if stated != built:
        raise BenchError(f"the configuration states {stated}; "
                         f"{c['client_model']} runs {built}")
    precision(c)
    adam(c)


def client_model(ctx):
    """The configuration's CNN as the program's ``Model``: its forward
    from the model file, multiplying at the run's precision, and the
    program's own loss and accuracy."""
    from repro.configs.base import ModelConfig
    from repro.models.model import Model, accuracy, cross_entropy

    c, forward = ctx.config, ctx.model.forward
    shapes = ctx.model.param_shapes()
    mm = reference.Matmul(precision=None)

    def fwd(params, batch):
        return forward(params, batch["images"], mm), jnp.zeros((), jnp.float32)

    def loss(params, batch):
        logits, _ = fwd(params, batch)
        ce = cross_entropy(logits, batch["labels"])
        return ce, {"loss": ce, "ce": ce,
                    "acc": accuracy(logits, batch["labels"])}

    def init(key):
        return jax.tree.map(lambda x: x[0],
                            reference.init_params(shapes, key, 1))

    cfg = ModelConfig(arch_id=c["client_model"], family="cnn", n_layers=0,
                      d_model=0, vocab_size=c["classes"], dtype=c["dtype"],
                      param_dtype=c["dtype"], scan_layers=False)
    return Model(cfg, init, fwd, loss)


def build(ctx, clinics, params):
    """The program's ``SwarmTrainer`` over the clinics, holding
    ``params`` and a fresh optimizer state."""
    from repro.configs.base import OptimizerConfig, SwarmConfig
    from repro.core.swarm import SwarmTrainer

    c = ctx.config
    swarm = SwarmConfig(n_clients=len(clinics), n_clusters=c["k"],
                        p1=c["p1"], p2=c["p2"], local_epochs=c["local_epochs"],
                        kmeans_iters=c["kmeans_iters"])
    trainer = SwarmTrainer(client_model(ctx), clinics, swarm,
                           OptimizerConfig(**c["optimizer"]),
                           jax.random.PRNGKey(0), batch_size=c["batch"],
                           aggregation="bso", use_pallas=c["use_pallas"])
    trainer.state = trainer.state._replace(
        params=params, opt_state=jax.vmap(trainer.opt.init)(params))
    return trainer


def fit_round(trainer, key=None):
    """One call of the timed path: ``fit`` for one round, continuing the
    state's own key chain unless ``key`` starts it."""
    return trainer.fit(trainer.state.key if key is None else key, 1)[-1]


def local_steps(ctx, clinics) -> int:
    c = ctx.config
    mean_n = np.mean([x["n_train"] for x in clinics])
    return c["local_epochs"] * int(np.ceil(mean_n / c["batch"]))


def round_kwargs(ctx, clinics, mm=reference.Matmul(), keep: int = 0) -> dict:
    """``reference.swarm_round``'s settings for this cell. ``mm`` and
    ``keep`` are for the controls and the planted faults
    (``chipbench/calibrate.py``)."""
    c = ctx.config
    return dict(forward=ctx.model.forward, mm=mm,
                local_steps=local_steps(ctx, clinics), batch=c["batch"],
                k=c["k"], iters=c["kmeans_iters"], p1=c["p1"], p2=c["p2"],
                adam=adam(c), keep=keep)


def run_reference(ctx, clinics, p0: dict, round_key, calls: int, **how):
    """The reference's readings over the first ``calls`` rounds, in the
    form ``check.compare`` takes; ``how`` goes to ``round_kwargs``."""
    sw = reference.make_swarm(clinics)
    state = reference.fresh_state(
        reference.unflatten({k: jnp.asarray(v) for k, v in p0.items()}),
        jnp.asarray(round_key))
    kw = round_kwargs(ctx, clinics, **how)
    out = {"losses": [], "val": [], "params": [], "assign": [], "p0": p0}
    for _ in range(calls):
        state, r = reference.swarm_round(state, sw, **kw)
        out["losses"].append(float(r.loss))
        out["val"].append(float(jnp.mean(r.val_acc)))
        out["assign"].append(np.asarray(r.assignments).tolist())
        out["params"].append(host_tree(state.params))
        if "grad_norms" not in out:
            out["grad_norms"] = {k: float(v) for k, v in r.grad_norms.items()}
    return out


class Setup(NamedTuple):
    trainer: object           # the program's trainer, after the checked calls
    clinics: list
    p0: dict                  # path -> initial weights (host)
    round_key: np.ndarray     # the state's first key (host)
    prog: dict                # the checked calls' readings


def setup(ctx) -> Setup:
    """Inputs and weights from the seed, the trainer, and the first
    ``check_calls`` calls of its fit."""
    c = ctx.config
    check_config(ctx)
    # numpy seeds must be non-negative; any whole number maps to one
    clinics = dr_data.make_clinics(dr_data.clinic_table(c), c["image_size"],
                                   ctx.seed % 2**64, c["split"])
    wkey, rkey = seed_keys(ctx.seed)
    params = jax.jit(lambda k: reference.init_params(
        ctx.model.param_shapes(), k, len(clinics)))(wkey)
    p0 = host_tree(params)
    trainer = build(ctx, clinics, params)
    if trainer.engine_cfg.local_steps != local_steps(ctx, clinics):
        raise BenchError(f"the trainer takes {trainer.engine_cfg.local_steps}"
                         f" local steps; {c['local_epochs']} epoch(s) of the "
                         f"mean clinic are {local_steps(ctx, clinics)}")
    prog = {"losses": [], "val": [], "params": [], "assign": []}
    with jax.default_matmul_precision(precision(c)):
        for i in range(ctx.traffic["check_calls"]):
            log = fit_round(trainer, rkey if i == 0 else None)
            prog["losses"].append(log.train_loss)
            prog["val"].append(log.mean_val_acc)
            prog["assign"].append(log.assignments.tolist())
            prog["params"].append(host_tree(trainer.state.params))
    return Setup(trainer, clinics, p0, np.asarray(rkey), prog)


def window(ctx, trainer, *, seconds=None, calls=None):
    """Calls back to back until ``seconds`` have passed or ``calls`` are
    made; returns (calls, rounds whose loss is not finite, seconds)."""
    n, failed = 0, 0
    with jax.default_matmul_precision(precision(ctx.config)):
        t0 = time.perf_counter()
        while True:
            log = fit_round(trainer)
            n += 1
            failed += int(not np.isfinite(log.train_loss))
            if (calls is not None and n >= calls) or (
                    seconds is not None
                    and time.perf_counter() - t0 >= seconds):
                break
        jax.block_until_ready(trainer.state)
        return n, failed, time.perf_counter() - t0


def run(ctx) -> dict:
    counter, gc_clock = CompileCounter(), GcClock()
    su = setup(ctx)
    # what set-up leaves (JAX's traced programs above all) moves out of
    # the collector's reach: a full collection over it between two
    # rounds stalled the chip for 1-2 s in some runs
    gc.collect()
    gc.freeze()
    setup_s = ctx.process_age_s()
    counter.on = gc_clock.on = True
    if ctx.args.trace:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        with jax.profiler.trace(str(ctx.trace_dir)):
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                rounds, failed, window_s = window(
                    ctx, su.trainer, calls=ctx.traffic["trace_calls"])
    else:
        rounds, failed, window_s = window(ctx, su.trainer,
                                          seconds=ctx.seconds)
    counter.on = gc_clock.on = False
    print(f"[chipbench] window: {gc_clock.n} garbage collections, "
          f"{gc_clock.s:.4f} s", file=sys.stderr, flush=True)
    if counter.n:
        raise BenchError(f"{counter.n} programs compiled inside the window")
    res = {"attempted": rounds, "failed": failed,
           "memory_peak_bytes": peak_bytes(ctx.devices)}
    res["metrics"] = {"sim_round_ms": window_s / rounds * 1e3,
                      "setup_s": setup_s}
    print(f"[chipbench] {ctx.cell['name']}: set-up {setup_s:.3f} s, "
          f"{rounds} rounds in {window_s:.3f} s", flush=True)

    clinics, p0, round_key, prog = su.clinics, su.p0, su.round_key, su.prog
    del su
    gc.unfreeze()
    gc.collect()

    if ctx.args.trace:
        tr_ = trace.load(str(ctx.trace_dir))
        res.update(busy_s=trace.mean_busy_s(tr_), window_s=tr_.window_s,
                   breakdown={"device_ops": trace.top_ops(tr_),
                              "idle_gaps": trace.idle_gaps(tr_)})
        res["layer_ctx"] = LayerContext(ctx, tr_, rounds, clinics)

    ref = run_reference(ctx, clinics, p0, round_key,
                        ctx.traffic["check_calls"])
    gaps = check.compare(prog, ref, tuple(ctx.traffic["checked_changes"]))
    res["correct"], res["checks"] = check.judge(gaps, ctx.limits)
    rest = {k: v for k, v in gaps.items() if k not in ctx.limits}
    print(f"[chipbench] not compared: {rest} "
          f"{check.diagnostics(prog, ref)}", file=sys.stderr, flush=True)
    return res


class LayerContext:
    """What a per-layer reader of this traffic sees."""

    def __init__(self, ctx, tr_, rounds, clinics):
        self.trace, self.rounds = tr_, rounds
        self.config, self.model, self.peaks = ctx.config, ctx.model, ctx.peaks
        self.chips = len(ctx.devices)
        self.n_clients = len(clinics)
        self.local_steps = local_steps(ctx, clinics)
        self.val_images = sum(len(x["val"][1]) for x in clinics)
