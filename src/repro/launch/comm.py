"""Communication accounting for the swarm — the §I/§III.B ledger.

The paper's scalability claim is a *traffic* claim: BSO-SL's
coordinator sees only O(#tensors) distribution summaries per client
while the model exchange stays peer-to-peer inside clusters. This
module turns that claim into measured numbers for a compiled fleet
round:

* :func:`collective_bytes` — census of the cross-device collectives in
  optimized HLO (per-device bytes per round). In the fleet regime the
  Eq. 2 ``cluster_fedavg`` segment-sum is what XLA partitions into
  all-reduce/all-gather traffic over the ``pod`` (client) axis, so
  this is the measured "aggregation traffic" of the round program.
* :func:`fleet_round_comm` — the full per-round ledger of one compiled
  fleet round step: the host-facing stat upload / cluster feedback
  (tiny, O(clients)) versus the on-mesh aggregation traffic (measured
  from the HLO, bounded analytically), plus the blockchain-SL and
  FedAvg baselines the paper compares against.

Deliberately side-effect free (no XLA_FLAGS mutation at import — cf.
``repro.launch.dryrun``, which historically owned the HLO parser and
now imports it from here) so the fleet driver and benchmarks can use
it without touching backend state.
"""
from __future__ import annotations

import re

from repro.core.diststats import full_params_bytes, upload_bytes

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "pred": 1,
                "s8": 1, "u8": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
                "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shard bytes of every collective op in optimized HLO.
    Returns {op_name: bytes, ..., "total": bytes} (per device)."""
    out = {c: 0 for c in _COLLECTIVES}
    n_ops = {c: 0 for c in _COLLECTIVES}
    # e.g.:  %all-reduce.5 = f32[2048,512]{1,0} all-reduce(...)
    pat = re.compile(
        r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+(" + "|".join(_COLLECTIVES) + r")\(")
    # tuple-result collectives:  = (f32[8]{0}, f32[8]{0}) all-to-all(
    # — greedy up to the op, since TPU layouts nest parentheses in the
    # tuple: f32[12,32]{1,0:T(8,128)S(1)}
    tup = re.compile(
        r"=\s*\((.*)\)\s+(" + "|".join(_COLLECTIVES) + r")\(")
    for line in hlo_text.splitlines():
        m = pat.search(line)
        if m:
            dt, dims, op = m.group(1), m.group(2), m.group(3)
            size = _DTYPE_BYTES.get(dt, 4)
            for d in dims.split(","):
                if d:
                    size *= int(d)
            out[op] += size
            n_ops[op] += 1
            continue
        m = tup.search(line)
        if m:
            parts, op = m.group(1), m.group(2)
            for shp in re.finditer(r"([a-z0-9]+)\[([0-9,]*)\]", parts):
                dt, dims = shp.group(1), shp.group(2)
                size = _DTYPE_BYTES.get(dt, 4)
                for d in dims.split(","):
                    if d:
                        size *= int(d)
                out[op] += size
            n_ops[op] += 1
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    out["op_counts"] = n_ops
    return out


def _cost(compiled):
    """XLA's flops / bytes-accessed estimate of ``compiled``, or None
    when the backend reports none."""
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) and ca else ca
    if not ca:
        return None
    return {k: float(ca[k]) for k in ("flops", "bytes accessed") if k in ca}


def fleet_round_comm(compiled, params_abs, n_clients: int,
                     batch_bytes: int = 0) -> dict:
    """Per-round communication ledger of ONE compiled fleet round step.

    ``compiled`` is the executable from ``fleet_setup(...).jit_fn
    .lower(...).compile()``; ``params_abs`` the (un-stacked) abstract
    single-client param pytree; ``batch_bytes`` optionally records the
    per-round data upload (client-local minibatches entering the mesh —
    not model traffic, listed separately for honesty).

    Host-facing traffic (the coordinator round-trip, all O(clients)):

    * ``stat_upload_bytes``    — the (N, 2*#tensors) matrix pulled to
      host each round (paper §III.B: the ONLY model-derived upload),
    * ``val_upload_bytes``     — the (N,) val scores the BSA ranks,
    * ``cluster_feedback_bytes`` — the (N,) int32 next-round clusters
      pushed back (plus the (N,) float32 Eq. 2 weights, constant).

    On-mesh traffic (the Eq. 2 exchange — stays client-to-client):

    * ``eq2_collective_bytes`` — measured per-device collective bytes
      parsed from the compiled round's optimized HLO
      (:func:`collective_bytes`; includes the op census),
    * ``eq2_p2p_bound_bytes``  — the analytic 2·N·P·itemsize
      intra-cluster exchange bound used by the §I comparison,
    * ``fedavg_bytes`` / ``blockchain_bytes`` — the server (2·N·P) and
      all-broadcast (N·(N−1)·P) baselines for the same model.

    ``cost_analysis`` carries XLA's own flops / bytes-accessed estimate,
    or None when the backend provides none.
    """
    up = upload_bytes(params_abs)
    full = full_params_bytes(params_abs)
    return {
        "n_clients": n_clients,
        "stat_upload_bytes": n_clients * up,
        "val_upload_bytes": n_clients * 4,
        "cluster_feedback_bytes": n_clients * (4 + 4),
        "batch_upload_bytes": int(batch_bytes),
        "eq2_collective_bytes": collective_bytes(compiled.as_text()),
        "eq2_p2p_bound_bytes": 2 * n_clients * full,
        "fedavg_bytes": 2 * n_clients * full,
        "blockchain_bytes": n_clients * (n_clients - 1) * full,
        "full_params_bytes": full,
        "coord_reduction_x": full / max(up, 1),
        "cost_analysis": _cost(compiled),
    }


def hier_host_bytes(params_abs, n_clients: int, n_pods: int,
                    k_local: int) -> dict:
    """The analytical host-facing ledger of ONE two-tier round, and the
    flat O(clients) round it replaces — pure arithmetic on the abstract
    params, no compiled program required (the extrapolation half of the
    ``BENCH_hier.json`` scaling claim; :func:`hier_round_comm` attaches
    the same numbers to a measured round).

    Upload (device -> host), per round:

    * flat: every client sends its (2*#tensors,) stat row plus a f32
      val score — ``N * (up + 4)``.
    * hier: only the ``S = n_pods * k_local`` pod-cluster summaries
      cross — per row the centroid (``up`` bytes) plus three f32
      scalars (count, weight sum, val sum) — ``S * (up + 12)`` (plus
      two O(1) scalars, mean val + loss, counted separately).

    Feedback (host -> device), per round:

    * flat: the (N,) int32 cluster decision + (N,) f32 Eq. 2 weights.
    * hier: the (S,) int32 pod-cluster -> global-cluster map ``g`` plus
      the O(1) ``use_composed`` flag and the 8-byte k-means key — the
      (N,) fallback/feedback arrays live on-device and never move.
    """
    up = upload_bytes(params_abs)
    S = n_pods * k_local
    return {
        "n_clients": n_clients,
        "n_pods": n_pods,
        "k_local": k_local,
        "summary_rows": S,
        "flat_upload_bytes": n_clients * (up + 4),
        "flat_feedback_bytes": n_clients * (4 + 4),
        "summary_upload_bytes": S * (up + 12),
        "scalar_upload_bytes": 8,
        "hier_feedback_bytes": S * 4 + 9,
        "hier_reduction_x": (n_clients * (up + 4))
        / max(S * (up + 12), 1),
    }


def hier_round_comm(compiled, params_abs, n_clients: int, *, n_pods: int,
                    k_local: int, batch_bytes: int = 0) -> dict:
    """Per-round ledger of ONE compiled HIERARCHICAL fleet round step —
    the two-tier counterpart of :func:`fleet_round_comm`.

    Host-facing traffic is the :func:`hier_host_bytes` arithmetic (the
    O(pods) summaries up, the (S,) map ``g`` down); the on-mesh Eq. 2
    exchange, the §I baselines and XLA's cost analysis are measured the
    same way as the flat ledger. The pod-local k-means adds NO host
    traffic at all — it runs inside the round program; its cost shows
    up only in ``cost_analysis``/``eq2_collective_bytes``.
    """
    full = full_params_bytes(params_abs)
    out = hier_host_bytes(params_abs, n_clients, n_pods, k_local)
    out.update({
        "batch_upload_bytes": int(batch_bytes),
        "eq2_collective_bytes": collective_bytes(compiled.as_text()),
        "eq2_p2p_bound_bytes": 2 * n_clients * full,
        "fedavg_bytes": 2 * n_clients * full,
        "blockchain_bytes": n_clients * (n_clients - 1) * full,
        "full_params_bytes": full,
        "cost_analysis": _cost(compiled),
    })
    return out


def hier_scaling_table(params_abs, *, pod_size: int, k_local: int,
                       n_clients=(10_000, 100_000, 1_000_000)) -> list:
    """Analytical extrapolation of the per-round host-facing bytes to
    swarm sizes no host could serve flat — one :func:`hier_host_bytes`
    row per N at fixed pod size (so pods grow with N and the hier curve
    stays O(N / pod_size) while flat is O(N)). This is the ledger the
    measured small-N slope in ``benchmarks/hier_bench.py`` is checked
    against."""
    rows = []
    for n in n_clients:
        n = int(n)
        pods = -(-n // pod_size)
        row = hier_host_bytes(params_abs, n, pods, k_local)
        row["pod_size"] = pod_size
        rows.append(row)
    return rows
