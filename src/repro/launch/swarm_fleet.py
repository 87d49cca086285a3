"""Fleet-regime BSO-SL: the paper's protocol as a multi-pod collective
program, lowered from the SAME round body as the sim regime
(``repro.core.engine.make_fleet_round``).

One swarm client per pod; within a pod the client's model is FSDP/TP-
sharded over (data, model). The round's communication:

  * distribution-stat upload  -> computed INSIDE the round program
    (``param_stats_batched`` under ``--pallas-stats``, the jnp oracle
    otherwise) and returned as a tiny (clients, 2*#tensors) matrix —
    the paper's communication-efficiency claim riding the same ICI/DCN
    collective as the round step instead of a separate host pass
  * intra-cluster FedAvg Eq.2 -> cluster-masked traffic over "pod"
    (client-to-client, no server): ``cluster_fedavg`` segment-sum, with
    XLA SPMD inserting the cross-pod collectives. (The explicit
    masked-psum shard_map formulation in core.aggregation is the same
    math and is exercised at unit scale in tests; XLA's partitioner
    cannot yet mix manual "pod" collectives with auto-sharded gathers
    at 512 devices — this is the one deliberate aggregation choice.)

The coordinator decisions (k-means + brain storm) stay host-side — they
are O(clients) on the uploaded stats and correspond to the paper's
neighbour-assignment server. This module lowers+compiles the fleet
round step on the 2x16x16 mesh — the beyond-paper "swarm-on-pods"
dry-run artifact.
"""
import argparse
import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import OptimizerConfig
from repro.core.engine import FleetRoundOut, HierRoundOut, make_fleet_round
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.optim.optimizers import make_optimizer
from repro.sharding import build_param_specs, use_sharding
from repro.sharding.rules import AxisRules, DEFAULT_LOGICAL_TO_PHYSICAL


def force_host_device_count(n: int = 512):
    """Opt into the n-device CPU stand-in. Deliberately NOT a module
    side effect: only the CLI entrypoint calls this, so importing this
    module (tests, examples) never poisons the process-wide backend.
    Must run before jax initialises its backend to take effect."""
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
        f" --xla_force_host_platform_device_count={n}"


def fleet_inner_rules() -> AxisRules:
    """Per-client sharding rules: the ``pod`` axis is the swarm-client
    axis in the fleet regime, so the inner (within-client) model
    sharding must never consume it."""
    return AxisRules({
        kk: tuple(a for a in v if a != "pod")
        for kk, v in DEFAULT_LOGICAL_TO_PHYSICAL.items()})


class FleetProgram(NamedTuple):
    """The one compiled-surface contract shared by the dry-run lowering
    and the multi-round driver (see :func:`fleet_setup`)."""
    jit_fn: Any          # jax.jit-wrapped engine.make_fleet_round step
    rules: AxisRules     # inner rules — trace under use_sharding(mesh, rules)
    in_shardings: Any    # per-argument shardings (batch/val are prefix trees)
    out_shardings: Any


def fleet_setup(model, opt, mesh, *, k: int, n_local_steps: int = 1,
                use_pallas_stats: bool = False, with_eval: bool = False,
                with_loss: bool = False, donate: bool = False,
                spmd: str = "auto", with_churn: bool = False,
                hier_k_local: int = 0,
                hier_kmeans_iters: int = 20) -> FleetProgram:
    """ONE setup path for the fleet round on a ``pod``-axis mesh —
    the dry-run lowering (:func:`lower_fleet_round`) and the end-to-end
    driver (``repro.launch.fleet_driver``) both build their program
    here, so the two can never drift.

    Two partitioning strategies over the same
    ``engine.make_fleet_round`` body:

    * ``spmd="auto"`` (the LM dry-run path) — GSPMD auto-partitioning:
      every client-stacked argument is sharded ``P("pod", ...)``,
      params and opt state additionally carry the inner FSDP/TP spec
      from :func:`fleet_inner_rules`, and Eq. 2's segment-sum is
      partitioned by XLA into the cross-pod collectives.
    * ``spmd="shard_map"`` (the driver path) — manual ``pod``
      collectives: the round body runs on each shard's *local* client
      slice (``axis_name="pod"``) and Eq. 2 is the explicit masked-psum
      formulation (``aggregation.cluster_fedavg_psum``). This is the
      layout that serves vmapped-*conv* clients (the paper's CNNs):
      GSPMD cannot partition the grouped convolution a vmapped conv
      lowers to over the stacked-client axis, while under shard_map
      each shard sees a plain per-client conv. Inner model sharding is
      not used on this path (CNN clients are single-device sized).

    ``with_eval`` keeps the per-client val accuracies in-program over a
    rectangular stacked val split; ``with_loss`` is the bucketed-eval
    driver surface (``engine.make_fleet_round(with_loss=True)``): the
    round program carries no val stack — the driver evaluates with one
    fixed-shape compiled program per size bucket — and returns the
    replicated last-step loss alongside the stats.

    ``with_churn`` appends the fault-injection operands — two (N,)
    bool masks ``(present, agg_present)`` sharded over the client axis
    (see ``engine.make_fleet_round(with_churn=True)``); the driver's
    quorum/staleness regime feeds them per round, and all-ones masks
    reproduce the churn-free program bitwise.

    ``hier_k_local > 0`` selects the HIERARCHICAL round surface
    (``engine.make_fleet_round(hier_k_local=...)``, exclusive with
    ``with_eval``/``with_loss``): pod-local k-means runs on-mesh and
    only the O(pods * k_local) :class:`~repro.core.engine.HierRoundOut`
    summaries face the host. On the shard_map path each mesh shard is
    one pod (pod index = ``axis_index("pod")``); on the GSPMD path the
    client axis is split into ``mesh.shape["pod"]`` equal contiguous
    pods. The per-round host traffic drops from O(clients) stats to
    O(pods) summaries in both directions (the decision comes back as
    the (pods * k_local,) map ``g``; the (N,) fallback ``clusters0``
    and the assignment feedback ``a_prev``/``a_local`` stay
    device-resident) — the scaling claim ``BENCH_hier.json`` measures.
    ``with_churn`` here appends THREE masks ``(present, agg_present,
    report)`` — see the engine docstring for the straggler semantics.

    The coordinator inputs (``clusters``, ``weights``) ride the client
    axis and the stat upload comes back sharded over ``pod``.
    ``donate=True`` donates the params/opt buffers (the driver's round
    loop updates the swarm in place, round after round, without
    retracing — the jit-cache contract ``tests/test_fleet.py`` pins).

    Call :attr:`FleetProgram.jit_fn` (or ``.lower(...)`` it) inside
    ``with mesh, use_sharding(mesh, program.rules):`` so activation
    constraints resolve against the fleet mesh.
    """
    rules = fleet_inner_rules()
    rep = jax.sharding.NamedSharding(mesh, P())
    # the uploaded stats matrix is O(clients * #tensors) — sharded over
    # the client axis like everything else in the round
    ssh = jax.sharding.NamedSharding(mesh, P("pod"))

    if with_eval and with_loss:
        raise ValueError("with_eval and with_loss are exclusive round "
                         "surfaces")
    hier = hier_k_local > 0
    if hier and (with_eval or with_loss):
        raise ValueError("hier_k_local selects its own eval surface — "
                         "drop with_eval/with_loss")
    if spmd == "shard_map":
        inner_step = make_fleet_round(model, opt, k, n_local_steps,
                                      use_pallas=use_pallas_stats,
                                      with_eval=with_eval,
                                      with_loss=with_loss,
                                      axis_name="pod",
                                      with_churn=with_churn,
                                      hier_k_local=hier_k_local,
                                      hier_kmeans_iters=hier_kmeans_iters)

        def local_step(*args):
            # every mesh axis is manual inside the shard_map body, so
            # with_sharding_constraint is rejected there — disable the
            # activation-sharding context for the traced body (matters
            # for attention-family clients whose forward calls
            # shard_act; conv clients never hit it)
            with use_sharding(None):
                return inner_step(*args)

        pod = P("pod")
        if hier:
            # (params, opt, batch, val, lr, g, use_composed, clusters0,
            #  a_prev, kmkey, weights) — g/use_composed/kmkey replicated
            # (the O(pods) decision), the fallback + assignment feedback
            # device-resident on the client axis
            in_specs = (pod, pod, pod, pod, P(), P(), P(), pod, pod,
                        P(), pod)
            out_specs = (pod, pod, HierRoundOut(
                centroids=pod, counts=pod, wsums=pod, valsums=pod,
                a_local=pod, mean_val=P(), train_loss=P()))
        elif with_eval:
            in_specs = (pod, pod, pod, pod, P(), pod, pod)
            out_specs = (pod, pod, FleetRoundOut(stats=pod, val_acc=pod,
                                                 train_loss=P()))
        elif with_loss:
            in_specs = (pod, pod, pod, P(), pod, pod)
            out_specs = (pod, pod, pod, P())
        else:
            in_specs = (pod, pod, pod, P(), pod, pod)
            out_specs = (pod, pod, pod)
        if with_churn:
            # present, agg_present (+ report on the hier surface)
            in_specs = in_specs + ((pod, pod, pod) if hier
                                   else (pod, pod))
        # check_vma off: several conv/reduce-window primitives lack
        # varying-manual-axes rules
        round_step = jax.shard_map(local_step, mesh=mesh,
                                   in_specs=in_specs, out_specs=out_specs,
                                   check_vma=False)
        to_shard = lambda spec: rep if spec == P() else ssh
        in_sh = jax.tree.map(to_shard, in_specs,
                             is_leaf=lambda x: isinstance(x, P))
        out_sh = jax.tree.map(to_shard, out_specs,
                              is_leaf=lambda x: isinstance(x, P))
    else:
        params_abs = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0)))
        opt_abs = jax.eval_shape(opt.init, params_abs)

        def stacked_shardings(tree_abs):
            return jax.tree.map(
                lambda s: jax.sharding.NamedSharding(
                    mesh, P(*("pod",) + tuple(s))),
                build_param_specs(tree_abs, mesh, rules))

        psh = stacked_shardings(params_abs)
        osh = stacked_shardings(opt_abs)
        # prefix shardings: one entry covers every batch/val leaf
        bsh = jax.sharding.NamedSharding(mesh, P("pod", "data"))
        round_step = make_fleet_round(model, opt, k, n_local_steps,
                                      use_pallas=use_pallas_stats,
                                      with_eval=with_eval,
                                      with_loss=with_loss,
                                      with_churn=with_churn,
                                      hier_k_local=hier_k_local,
                                      hier_pods=mesh.shape["pod"],
                                      hier_kmeans_iters=hier_kmeans_iters)
        if hier:
            # (params, opt, batch, val, lr, g, use_composed, clusters0,
            #  a_prev, kmkey, weights): client-axis operands sharded,
            # the O(pods) decision + summaries replicated
            in_sh = (psh, osh, bsh, ssh, None, rep, rep, ssh, ssh,
                     rep, rep)
            out_sh = (psh, osh, HierRoundOut(
                centroids=rep, counts=rep, wsums=rep, valsums=rep,
                a_local=ssh, mean_val=rep, train_loss=rep))
        elif with_eval:
            in_sh = (psh, osh, bsh, ssh, None, rep, rep)
            out_sh = (psh, osh, FleetRoundOut(stats=ssh, val_acc=ssh,
                                              train_loss=rep))
        elif with_loss:
            in_sh = (psh, osh, bsh, None, rep, rep)
            out_sh = (psh, osh, ssh, rep)
        else:
            in_sh = (psh, osh, bsh, None, rep, rep)
            out_sh = (psh, osh, ssh)
        if with_churn:
            # present, agg_present (+ report on the hier surface)
            in_sh = in_sh + ((rep, rep, rep) if hier else (rep, rep))
    jit_fn = jax.jit(round_step, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=(0, 1) if donate else ())
    return FleetProgram(jit_fn=jit_fn, rules=rules, in_shardings=in_sh,
                        out_shardings=out_sh)


def lower_fleet_round(arch_id: str = "granite-3-2b", k: int = 3,
                      seq: int = 1024, per_client_batch: int = 16,
                      use_pallas_stats: bool = False):
    cfg = get_config(arch_id)
    import dataclasses
    cfg = dataclasses.replace(cfg, dtype="bfloat16", scan_layers=True,
                              remat="full")
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=True)
    n_clients = mesh.shape["pod"]
    opt = make_optimizer(OptimizerConfig(name="adamw", lr=3e-4))

    params_abs = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    opt_abs = jax.eval_shape(opt.init, params_abs)

    def stack(t):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((n_clients,) + x.shape, x.dtype), t)

    sparams, sopt = stack(params_abs), stack(opt_abs)
    batch_abs = {
        "tokens": jax.ShapeDtypeStruct((n_clients, per_client_batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((n_clients, per_client_batch, seq), jnp.int32),
    }
    clusters_abs = jax.ShapeDtypeStruct((n_clients,), jnp.int32)
    weights_abs = jax.ShapeDtypeStruct((n_clients,), jnp.float32)

    program = fleet_setup(model, opt, mesh, k=k,
                          use_pallas_stats=use_pallas_stats)
    with mesh, use_sharding(mesh, program.rules):
        lowered = program.jit_fn.lower(
            sparams, sopt, batch_abs,
            jax.ShapeDtypeStruct((), jnp.float32),
            clusters_abs, weights_abs)
        compiled = lowered.compile()
    return lowered, compiled


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--pallas-stats", action="store_true",
                    help="serve the in-round stat upload with the "
                         "param_stats_batched kernel (TPU; CPU runs it "
                         "in interpret mode)")
    args = ap.parse_args()
    force_host_device_count(512)
    _, compiled = lower_fleet_round(args.arch,
                                    use_pallas_stats=args.pallas_stats)
    mem = compiled.memory_analysis()
    print(f"[swarm-fleet] {args.arch} round step compiled on 2x16x16; "
          f"temp/dev={int(mem.temp_size_in_bytes)/2**30:.2f} GiB")


if __name__ == "__main__":
    main()
