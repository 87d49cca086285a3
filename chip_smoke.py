"""Chip smoke test: the BSO-SL main path on a TPU, checked end to end.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the multi-pod fleet round

One chip, in order:

1. device gate — a TPU or a non-zero exit, no CPU fallback;
2. the ``param_stats_batched`` and ``kmeans_assign`` Pallas kernels,
   compiled (``tpu_custom_call`` in the lowered program) at the shapes
   the fit uses and checked against ``repro/kernels/ref.py``;
3. the sim-engine fit: ``squeezenet-dr`` clients on the full Table-I
   swarm (3,657 images, 14 clinics, 32 px), k=3, p1=0.9, p2=0.8,
   3 rounds of ``jit_run_rounds`` with ``use_pallas=True``;
4. fleet -> checkpoint -> scoring: ``run_fleet`` on the fleet mesh (one
   compiled round program), the exported checkpoint loaded by
   ``repro.serve`` and a few held-out images classified.

``--chips 4`` runs only the multi-chip path: the first 12 clinics on a
4-pod mesh against the same run on one device.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed
only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from importlib import metadata
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import serve  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import OptimizerConfig, SwarmConfig  # noqa: E402
from repro.core.diststats import swarm_distribution_matrix  # noqa: E402
from repro.core.engine import (EngineConfig, jit_run_rounds,  # noqa: E402
                               make_swarm_data, make_swarm_state,
                               resolve_local_steps)
from repro.data.dr import make_dr_swarm_data, scale_table  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.kmeans_assign import kmeans_assign  # noqa: E402
from repro.kernels.param_stats import param_stats_batched  # noqa: E402
from repro.launch.fleet_driver import make_unit_fleet, run_fleet  # noqa: E402
from repro.launch.mesh import make_fleet_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim.optimizers import make_optimizer  # noqa: E402
from repro.utils.compile_cache import use_compile_cache  # noqa: E402

ARCH = "squeezenet-dr"
IMAGE_SIZE = 32
ROUNDS = 3
LR = 2e-3
BATCH = 16
SEED = 0
N_GRADES = 5
# kernel vs reference, both fp32 on the chip: summation order differs
STAT_RTOL, STAT_ATOL = 1e-4, 1e-5
FLEET_LOCAL_STEPS = 4
# 4-pod vs 1-device fleet. Eq. 2's cross-pod psum sums the clusters in
# another order than the 1-device program, so from round 1 on the
# aggregates differ by rounding, which the TPU's bf16 passes for f32
# convs widen. Adam's normalised step turns such a difference in a
# near-zero gradient into a step of up to lr either way (its step is
# bounded by lr when 1-b1 <= sqrt(1-b2): 0.1 <= 0.22 here), so after
# the first split an element may drift 2·lr per local step of the
# remaining rounds: PARAM_ATOL. Measured on four v5e chips: no fault
# 5.76e-3 max |diff| and 9.66e-4 relative L2 (8.43e-8 at precision
# "highest"); Eq. 2's cross-pod psum dropped 2.08 and 1.32; the 4-pod
# run one round short 1.73 and 0.544. Both limits sit between.
PARAM_ATOL = 2 * LR * FLEET_LOCAL_STEPS * (ROUNDS - 1)
PARAM_REL_L2 = 1e-2


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


def log(msg: str):
    print(f"[smoke] {msg}", flush=True)


def device_gate(n_chips: int):
    devs = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"jax {jax.__version__}, libtpu {libtpu}; devices: platform="
        f"{devs[0].platform} kind={devs[0].device_kind} count={len(devs)}")
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} devices, "
                 f"JAX found {len(devs)}")
    return devs[:n_chips]


def check_mosaic(hlo: str, what: str):
    check("tpu_custom_call" in hlo, f"{what} holds no Mosaic kernel")


def kernel_phase(stacked_params):
    """Both coordinator kernels at the fit's shapes against the refs.
    Returns the (N, 2*#tensors) feature matrix the kernels produced."""
    leaves = [x for x in jax.tree.leaves(stacked_params)
              if jnp.issubdtype(x.dtype, jnp.floating)]
    stats = jax.jit(lambda ls: [param_stats_batched(x, interpret=False)
                                for x in ls])
    lowered = stats.lower(leaves)
    check_mosaic(lowered.as_text(), "param_stats_batched")
    got = lowered.compile()(leaves)
    cols = []
    for x, (m, v) in zip(leaves, got):
        rm, rv = ref.ref_param_stats_batched(x)
        check(np.allclose(m, rm, rtol=STAT_RTOL, atol=STAT_ATOL)
              and np.allclose(v, rv, rtol=STAT_RTOL, atol=STAT_ATOL),
              f"param_stats_batched {x.shape} disagrees with the reference")
        cols += [m, jnp.log1p(v)]
    X = jnp.stack(cols, axis=1)
    log(f"param_stats_batched: {len(leaves)} client-stacked tensors "
        f"({leaves[0].shape[0]} clients) match ref")

    C = X[jnp.asarray([0, X.shape[0] // 2, X.shape[0] - 1])]
    assign = jax.jit(lambda X, C: kmeans_assign(X, C, interpret=False))
    lowered = assign.lower(X, C)
    check_mosaic(lowered.as_text(), "kmeans_assign")
    a = np.asarray(lowered.compile()(X, C))
    r = np.asarray(ref.ref_kmeans_assign(X, C))
    # a differing id must be a tie in exact (float64) distances
    d = ((np.asarray(X, np.float64)[:, None]
          - np.asarray(C, np.float64)[None]) ** 2).sum(-1)
    rows = np.arange(len(a))
    tie = np.abs(d[rows, a] - d[rows, r]) <= 1e-6 * max(d.max(), 1e-30)
    check(bool(np.all((a == r) | tie)),
          f"kmeans_assign {a.tolist()} disagrees with ref {r.tolist()}")
    log(f"kmeans_assign: X{tuple(X.shape)} x C{tuple(C.shape)} matches ref")
    return X


def sim_fit_phase(model, opt, clients):
    """3 rounds of the scanned engine fit with the Pallas stat path."""
    swarm = SwarmConfig()
    cfg = EngineConfig(model=model, opt=opt,
                       local_steps=resolve_local_steps(swarm, clients, BATCH),
                       batch_size=BATCH, lr=LR, aggregation="bso",
                       n_clusters=swarm.n_clusters, p1=swarm.p1, p2=swarm.p2,
                       kmeans_iters=swarm.kmeans_iters, use_pallas=True)
    data = make_swarm_data(model.cfg, clients)
    state = make_swarm_state(model, opt, clients, jax.random.PRNGKey(SEED))
    t0 = time.perf_counter()
    compiled = jit_run_rounds.lower(state, data, cfg, ROUNDS).compile()
    t1 = time.perf_counter()
    check_mosaic(compiled.as_text(), "the sim fit program")
    state, ms = compiled(state, data)
    jax.block_until_ready((state, ms))
    t2 = time.perf_counter()
    log(f"sim fit: {len(clients)} clinics, {cfg.local_steps} local steps "
        f"x {ROUNDS} rounds; compile {t1 - t0:.2f}s, "
        f"{(t2 - t1) / ROUNDS:.3f}s/round (information only)")
    loss, acc = np.asarray(ms.train_loss), np.asarray(ms.val_acc)
    assign = np.asarray(ms.assignments)
    check(loss.shape == (ROUNDS,) and np.isfinite(loss).all(),
          f"sim fit loss not finite: {loss}")
    check(np.isfinite(acc).all() and ((acc >= 0) & (acc <= 1)).all(),
          f"sim fit val acc out of [0, 1]: {acc}")
    check(((assign >= 0) & (assign < cfg.n_clusters)).all(),
          f"sim fit assignments out of [0, {cfg.n_clusters}): {assign}")
    f_pal = swarm_distribution_matrix(state.params, use_pallas=True)
    f_ref = swarm_distribution_matrix(state.params, use_pallas=False)
    check(np.allclose(f_pal, f_ref, rtol=STAT_RTOL, atol=STAT_ATOL),
          "final stats: use_pallas=True disagrees with use_pallas=False")
    log(f"sim fit: loss {loss.tolist()}, mean val acc "
        f"{np.asarray(ms.mean_val_acc).tolist()}, final clusters "
        f"{assign[-1].tolist()}")


def check_fleet_history(res, n_clusters: int = 3):
    check(res.n_compiles == 1, f"fleet compiled {res.n_compiles} programs")
    check_mosaic(res.compiled.as_text(), "the fleet round program")
    for h in res.history:
        check(np.isfinite(h.train_loss), f"fleet round {h.round} loss")
        check(((h.val_acc >= 0) & (h.val_acc <= 1)).all(),
              f"fleet round {h.round} val acc out of [0, 1]")
        check(((h.assignments >= 0) & (h.assignments < n_clusters)).all(),
              f"fleet round {h.round} assignments out of range")


def fleet_serve_phase(model, opt, mesh, clients, workdir: Path):
    """run_fleet -> checkpoint -> serve.load_checkpoint -> classify."""
    ckpt = workdir / "fleet"
    res = run_fleet(model, opt, mesh, clients, rounds=ROUNDS,
                    use_pallas_stats=True, ckpt_path=str(ckpt), seed=SEED,
                    lr=LR)
    check_fleet_history(res)
    check(ckpt.with_suffix(".npz").exists()
          and ckpt.with_suffix(".json").exists(), "checkpoint not written")
    log(f"fleet: {len(clients)} clinics on mesh {dict(mesh.shape)}, "
        f"{res.n_compiles} compile ({res.compile_s:.2f}s), mean val acc "
        f"{[round(a, 4) for a in res.mean_val_accs]}")

    served, params = serve.load_checkpoint(ckpt)
    images = [c["test"][0][0] for c in clients[:8]]
    out = serve.classify(served, params, images, batch_buckets=(8,))
    labels = np.asarray([o.label for o in out])
    conf = np.asarray([o.confidence for o in out])
    check(len(out) == len(images), "classify dropped requests")
    check(((labels >= 0) & (labels < N_GRADES)).all(),
          f"grades out of [0, {N_GRADES}): {labels}")
    check(np.isfinite(conf).all() and ((conf > 0) & (conf <= 1)).all(),
          f"confidences out of (0, 1]: {conf}")
    logits, _ = served.forward(params, {"images": jnp.asarray(images)})
    check(np.array_equal(labels, np.argmax(np.asarray(logits), -1)),
          "served grades differ from a direct forward")
    log(f"serve: {len(images)} held-out images graded {labels.tolist()}")


def multi_chip_phase(devices):
    """First 12 clinics: the 4-pod mesh against one device."""
    model, opt, mesh, clients = make_unit_fleet(
        12, image_size=IMAGE_SIZE, data_scale=1, seed=SEED, lr=LR)
    check(mesh.shape["pod"] == len(devices) == len(jax.devices()),
          f"fleet mesh {dict(mesh.shape)} does not span the "
          f"{len(devices)} devices")
    one = make_fleet_mesh(len(clients), devices=devices[:1])
    kw = dict(rounds=ROUNDS, local_steps=FLEET_LOCAL_STEPS,
              use_pallas_stats=True, seed=SEED, lr=LR)
    wide = run_fleet(model, opt, mesh, clients, **kw)
    narrow = run_fleet(model, opt, one, clients, **kw)
    for res in (wide, narrow):
        check_fleet_history(res)
    coll = wide.comm["eq2_collective_bytes"]["total"]
    same = [bool(np.array_equal(hw.assignments, hn.assignments))
            for hw, hn in zip(wide.history, narrow.history)]
    one_image = 1.0 / np.asarray([len(c["val"][1]) for c in clients])
    val_diff = max(float((np.abs(hw.val_acc - hn.val_acc) / one_image).max())
                   for hw, hn in zip(wide.history, narrow.history))
    pw = np.concatenate([np.ravel(x) for x in jax.tree.leaves(wide.params)])
    pn = np.concatenate([np.ravel(x)
                         for x in jax.tree.leaves(narrow.params)])
    pw, pn = pw.astype(np.float64), pn.astype(np.float64)
    rel = np.linalg.norm(pw - pn) / np.linalg.norm(pn)
    max_diff = float(np.abs(pw - pn).max())
    # logged before the checks, so a failing run still shows every number
    log(f"4 pods vs 1 device: assignments equal per round {same}, val acc "
        f"within {val_diff:.3g} images per clinic, final params max |diff| "
        f"{max_diff:.3g} (limit {PARAM_ATOL}), relative L2 {rel:.3g} (limit "
        f"{PARAM_REL_L2}); Eq. 2 collectives {coll} B/device")
    check(coll > 0, "the 4-pod round program has no cross-pod collective")
    check(all(same), f"assignments differ between 4 pods and 1 device: "
          f"{[h.assignments.tolist() for h in wide.history]} vs "
          f"{[h.assignments.tolist() for h in narrow.history]}")
    check(val_diff <= 1.0 + 1e-6,
          f"val acc differs by {val_diff} images in one clinic")
    check(np.allclose(pw, pn, rtol=0, atol=PARAM_ATOL),
          f"final params: max |diff| {max_diff:.3g} > {PARAM_ATOL}")
    check(rel <= PARAM_REL_L2,
          f"final params: relative L2 distance {rel:.3g} > {PARAM_REL_L2}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-pod fleet round and its "
                         "1-device comparison")
    args = ap.parse_args()
    devices = device_gate(args.chips)
    log(f"compile cache: {use_compile_cache()}")

    if args.chips == 4:
        multi_chip_phase(devices)
    else:
        clients = make_dr_swarm_data(image_size=IMAGE_SIZE, seed=SEED,
                                     table=scale_table(1))
        model = build_model(get_config(ARCH))
        opt = make_optimizer(OptimizerConfig(name="adam", lr=LR))
        keys = jax.random.split(jax.random.PRNGKey(SEED), len(clients))
        kernel_phase(jax.vmap(model.init)(keys))
        sim_fit_phase(model, opt, clients)
        workdir = ROOT / ".smoke_ckpt"
        shutil.rmtree(workdir, ignore_errors=True)
        try:
            fleet_serve_phase(*make_unit_fleet(
                len(clients), image_size=IMAGE_SIZE, data_scale=1,
                seed=SEED, lr=LR), workdir)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
