"""Readings from which a training cell's correctness limits are set.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3 [--out readings.json]

For every seed: the program's first calls, as a run of the cell makes
them in set-up, against the plain reference (the sound readings; the
largest over the seeds is a limit's lower reading). For each control
seed, stand-ins put in the program's place against the reference:

* ``control.1pass``: the reference with its convolutions and matrix
  products in one bfloat16 pass, as JAX multiplies float32 on a TPU by
  default (the program's own path without its "highest");
* ``control.3pass``: the same in three bfloat16 passes (JAX's "high");
* ``half_batch``: the reference training on half of each minibatch,
  the mean taken over the rest (a planted fault).

A state left unchanged reads 1 on every ``change_gap`` by definition and
needs no run. ``detail`` holds every call's loss gap and every leaf's
change gap, for the look at where a gap comes from. The benchmark's own
runs never run this; it needs the chips the cell asks for, as a run
does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import check, reference  # noqa: E402
from chipbench import run as bench  # noqa: E402

STAND_INS = {"control.1pass": {"mm": reference.Matmul(passes=1)},
             "control.3pass": {"mm": reference.Matmul(passes=3)}}


def readings(ctx, control: bool) -> dict:
    drv, tr = ctx.driver, ctx.traffic
    changes = tuple(tr["checked_changes"])
    su = drv.setup(ctx)
    args = (ctx, su.clinics, su.p0, su.round_key, tr["check_calls"])
    prog = su.prog
    del su
    ref = drv.run_reference(*args)
    out = {"sound": check.compare(prog, ref, changes),
           "diagnostics": {"sound": check.diagnostics(prog, ref)},
           "detail": {"sound": check.detail(prog, ref)}}
    if control:
        how = dict(STAND_INS, half_batch={"keep": ctx.config["batch"] // 2})
        for name, kw in how.items():
            got = drv.run_reference(*args, **kw)
            out[name] = check.compare(got, ref, changes)
            out["diagnostics"][name] = check.diagnostics(got, ref)
            out["detail"][name] = check.detail(got, ref)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    controls = {int(s) for s in a.control_seeds.split(",") if s}
    rows = []
    for seed in seeds + sorted(controls - set(seeds)):
        args = bench.parse(["--workload", a.workload, "--seed", str(seed),
                            "--seconds", "0"])
        ctx = bench.context(args)
        t0 = time.perf_counter()
        row = {"seed": seed, **readings(ctx, seed in controls),
               "seconds": time.perf_counter() - t0}
        rows.append(row)
        print(json.dumps(row), flush=True)
    worst = {}
    for kind in ("sound", *STAND_INS, "half_batch"):
        got = [r[kind] for r in rows if kind in r]
        if got:
            worst[kind] = {k: (max if kind == "sound" else min)(
                g[k] for g in got) for k in got[0]}
    print(json.dumps({"workload": a.workload, "seeds": len(rows),
                      "lower": worst.pop("sound", None),
                      "stand_in_min": worst}))
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(json.dumps({"rows": rows, "summary": worst},
                                          indent=1))


if __name__ == "__main__":
    main()
