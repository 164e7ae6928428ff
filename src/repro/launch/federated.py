"""Pod-level federated training driver: FedTest via shard_map, one client
per device along the ``clients`` mesh axis.

This is the datacenter deployment path of DESIGN.md §3 (the single-host
``launch/train.py`` driver is the simulation path); both routes drive
the *same* ``repro.core.engine.RoundProgram``, on the ring / allgather
exchange backends here and on the local vmap backend there. The full
adversarial scenario matrix runs on either: ``--attack`` /
``--malicious`` / ``--attack-scale`` resolve against the ``ATTACKS``
registry (corruption happens per device, before the model exchange) and
``--participation`` samples a client subset per round. On real hardware the mesh axis maps
onto TPU chips; in this container it runs on host-platform placeholder
devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \\
      python -m repro.launch.federated --clients 8 --rounds 4 \\
      --exchange ring --attack sign_flip --malicious 1 \\
      --participation 0.75

Named presets from ``repro.configs.scenarios`` run on the pod too —
``--scenario`` refits the preset to the device count
(``scenario_for_pod``); explicitly passed flags still override preset
fields, mirroring ``repro.launch.train``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Any, Iterator, NamedTuple, Optional, Sequence, Tuple

import numpy as np

# FedConfig fields the CLI leaves unset fall back to these (argparse
# defaults are None so --scenario can tell "explicitly passed" apart)
_FED_CLI_DEFAULTS = dict(
    num_malicious=0, attack="none", attack_kwargs={}, attack_scale=1.0,
    aggregator="fedtest", selector="rotating", participation=1.0,
    coalition="none", coalition_kwargs={}, coalition_size=0,
    fault="none", fault_kwargs={}, fault_rate=0.1,
    local_steps=6)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--population", type=int, default=None,
                    help="run the population tier (DESIGN.md §11): N "
                         "simulated clients, per-round compute on the "
                         "sampled cohort only, the [C] cohort axis "
                         "GSPMD-sharded across the --clients devices")
    ap.add_argument("--cohort", type=int, default=None,
                    help="cohort slot capacity C for --population; must "
                         "divide evenly across --clients devices. The "
                         "Bernoulli sampling rate is refit to C/N. "
                         "Errors loudly when C > N")
    ap.add_argument("--testers-from-cohort", action="store_true",
                    help="population tier: recruit the round's testing "
                         "committee from the sampled cohort instead of "
                         "the whole population (at C << N a "
                         "population-wide tester almost never "
                         "participates, so every report row is masked "
                         "and scoring degenerates; DESIGN.md §11)")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--exchange", default="ring",
                    choices=["ring", "allgather"],
                    help="cross-testing model exchange schedule "
                         "(EXPERIMENTS.md §Perf compares the two)")
    ap.add_argument("--scenario", default=None,
                    help="named FedConfig preset (repro.configs."
                         "scenarios), refitted to --clients devices; "
                         "explicit flags override preset fields")
    ap.add_argument("--aggregator", default=None,
                    help="repro.strategies.AGGREGATORS name (krum / "
                         "trimmed_mean / median all-gather flat updates; "
                         "trimmed_mean_coord / median_coord additionally "
                         "combine() them per-coordinate on the gathered "
                         "matrix, replicated across the pod)")
    ap.add_argument("--attack", default=None,
                    help="repro.strategies.ATTACKS name; corruption runs "
                         "per device before the model exchange")
    ap.add_argument("--malicious", type=int, default=None,
                    help="number of malicious clients (placement via "
                         "--attack-kwargs)")
    ap.add_argument("--attack-kwargs", default=None, type=json.loads,
                    help="JSON kwargs for the attack ctor, e.g. "
                         '\'{"placement": "first"}\'')
    ap.add_argument("--attack-scale", type=float, default=None)
    ap.add_argument("--participation", type=float, default=None,
                    help="per-round Bernoulli client-sampling fraction "
                         "R/N; non-sampled clients train nothing, report "
                         "nothing and get zero aggregation weight")
    ap.add_argument("--selector", default=None,
                    help="repro.strategies.SELECTORS name for the per-"
                         "round tester mask")
    ap.add_argument("--coalition", default=None,
                    help="repro.strategies.COALITIONS name "
                         "(DESIGN.md §7): coordinated members mount a "
                         "model attack and/or rewrite their tester rows "
                         "of the replicated accuracy matrix")
    ap.add_argument("--coalition-size", type=int, default=None,
                    help="number of coordinated members")
    ap.add_argument("--coalition-kwargs", default=None, type=json.loads,
                    help="JSON kwargs for the coalition ctor, e.g. "
                         '\'{"boost_to": 0.9}\'')
    ap.add_argument("--fault", default=None,
                    help="repro.strategies.FAULTS name (DESIGN.md §9): "
                         "availability fault ANDed into the "
                         "participation mask after tester selection")
    ap.add_argument("--fault-rate", type=float, default=None,
                    help="per-round drop probability for the fault model")
    ap.add_argument("--fault-kwargs", default=None, type=json.loads,
                    help="JSON kwargs for the fault ctor, e.g. "
                         '\'{"deadline": 2.0}\'')
    ap.add_argument("--compressor", default=None,
                    help="repro.strategies.COMPRESSORS name "
                         "(DESIGN.md §12): clients transmit encoded "
                         "deltas with per-client error feedback instead "
                         "of dense models; the round carries a "
                         "replicated [N, D] feedback buffer")
    ap.add_argument("--compressor-kwargs", default=None, type=json.loads,
                    help="JSON kwargs for the compressor ctor, e.g. "
                         '\'{"k": 0.05}\' (topk) or \'{"chunk": 256}\' '
                         "(int8)")
    ap.add_argument("--assert-malicious-below", type=float, default=None,
                    help="exit non-zero unless the final round's "
                         "malicious_weight is below this bar (the CI "
                         "coalition smoke gate)")
    ap.add_argument("--testers", type=int, default=None,
                    help="K testers per round (default: all clients)")
    ap.add_argument("--crosstest-impl", default=None,
                    choices=["batched", "reference"],
                    help="cross-testing dispatch model (DESIGN.md §10): "
                         "overlapped/batched fast path vs the reference "
                         "schedule (bit-identical)")
    ap.add_argument("--dataset", default="mnist_like",
                    choices=["mnist_like", "cifar_like"])
    ap.add_argument("--min-classes", type=int, default=None,
                    help="mildest shard skew: every client holds at "
                         "least this many classes (the dynamics bar of "
                         "EXPERIMENTS.md §Paper-validation uses 8 — with "
                         "near-single-class shards the tester accuracy "
                         "matrix is a lottery no scoring can separate)")
    ap.add_argument("--out", default="experiments/federated_pod")
    ap.add_argument("--seed", type=int, default=0)
    return ap


class PodRun(NamedTuple):
    """What :func:`build_pod` makes from the parsed flags."""

    model: Any          # repro.models.Model
    fed: Any            # FedConfig
    train: Any          # TrainConfig
    data: Any           # FederatedDataset
    round_fn: Any       # jitted shard_map round
    comp: Any           # initial [N, D] error-feedback buffer or None


def client_mesh(n: int):
    """The 1-D ``clients`` mesh over the first ``n`` devices."""
    import jax
    from jax.sharding import Mesh
    if len(jax.devices()) < n:
        raise SystemExit(f"need {n} devices, have {len(jax.devices())}; "
                         "set XLA_FLAGS before running")
    return Mesh(np.asarray(jax.devices()[:n]), ("clients",))


def build_pod(args: argparse.Namespace, mesh) -> PodRun:
    """Model, configs, client data and the jitted pod round."""
    import jax

    from repro.config import FedConfig, TrainConfig
    from repro.configs import get_config, scenario_for_pod
    from repro.core.engine import (
        init_comp_state, make_allgather_round, make_distributed_round)
    from repro.data import (CIFAR_LIKE, MNIST_LIKE,
                            make_federated_image_dataset)
    from repro.models import build_model

    N = mesh.shape["clients"]
    arch = ("fedtest-cnn-mnist" if args.dataset == "mnist_like"
            else "fedtest-cnn")
    cfg = get_config(arch).replace(cnn_channels=(8, 16, 16), cnn_hidden=32)
    model = build_model(cfg)

    passed = dict(num_testers=args.testers, num_malicious=args.malicious,
                  local_steps=args.local_steps,
                  aggregator=args.aggregator,
                  attack=args.attack, attack_kwargs=args.attack_kwargs,
                  attack_scale=args.attack_scale,
                  participation=args.participation,
                  selector=args.selector,
                  coalition=args.coalition,
                  coalition_size=args.coalition_size,
                  coalition_kwargs=args.coalition_kwargs,
                  fault=args.fault, fault_kwargs=args.fault_kwargs,
                  fault_rate=args.fault_rate,
                  compressor=args.compressor,
                  compressor_kwargs=args.compressor_kwargs,
                  crosstest_impl=args.crosstest_impl,
                  seed=args.seed)
    passed = {f: v for f, v in passed.items() if v is not None}
    if args.scenario:
        # preset refitted to the device count; explicit flags override
        fed = dataclasses.replace(scenario_for_pod(args.scenario, N),
                                  **passed)
    else:
        defaults = dict(_FED_CLI_DEFAULTS, num_testers=N)
        fed = FedConfig(num_users=N, **{**defaults, **passed})
    tc = TrainConfig(optimizer="sgd", lr=args.lr, schedule="constant",
                     batch_size=args.batch, grad_clip=0.0, remat=False)
    spec = MNIST_LIKE if args.dataset == "mnist_like" else CIFAR_LIKE
    pkw = ({"min_classes": args.min_classes,
            "max_classes": spec.num_classes}
           if args.min_classes is not None else None)
    data = make_federated_image_dataset(spec, N, num_samples=N * 250,
                                        global_test=400, seed=args.seed,
                                        partition_kwargs=pkw)

    make = (make_distributed_round if args.exchange == "ring"
            else make_allgather_round)
    round_fn = jax.jit(make(model, fed, tc, mesh,
                            counts=data.train.counts,
                            server_data=(data.server_x[:256],
                                         data.server_y[:256])))
    # compressed exchange (DESIGN.md §12): the round carries the
    # replicated [N, D] error-feedback buffer through the grown
    # round_fn signature; None (and the 8-arg form) when uncompressed
    return PodRun(model, fed, tc, data, round_fn,
                  init_comp_state(fed, model))


def pod_rounds(pod: PodRun, rounds: int, seed: int
               ) -> Iterator[Tuple[int, Any, dict]]:
    """Run ``rounds`` pod rounds; yields ``(round, params, metrics)``.

    The global model starts from ``model.init(PRNGKey(seed))`` and round
    ``r`` runs on the base key ``fold_in(PRNGKey(seed + 1), r)``.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.engine import round_keys
    from repro.core.scoring import init_scores
    from repro.data import sample_client_batches

    fed, tc, data = pod.fed, pod.train, pod.data
    params = pod.model.init(jax.random.PRNGKey(seed))
    scores = init_scores(fed.num_users)
    comp = pod.comp
    tx, ty = data.test.xs[:, :64], data.test.ys[:, :64]
    run_key = jax.random.PRNGKey(seed + 1)
    for r in range(rounds):
        # the engine derives the tester set and the participation mask
        # from the round key itself (repro.core.engine.round_keys); the
        # host only samples the training batches from the same bundle
        key = jax.random.fold_in(run_key, r)
        bx, by = sample_client_batches(round_keys(key).batch, data.train,
                                       fed.local_steps, tc.batch_size)
        if comp is not None:
            params, scores, comp, metrics = pod.round_fn(
                params, scores, comp, bx, by, tx, ty, key,
                jnp.asarray(r, jnp.int32))
        else:
            params, scores, metrics = pod.round_fn(
                params, scores, bx, by, tx, ty, key,
                jnp.asarray(r, jnp.int32))
        yield r, params, metrics


def main(argv: Optional[Sequence[str]] = None):
    args = build_parser().parse_args(argv)

    # the device count must be set before jax initialises
    if "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.clients}")

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax.numpy as jnp

    mesh = client_mesh(args.clients)
    if args.population is not None:
        _run_population(args, mesh)
        return

    pod = build_pod(args, mesh)
    fed, data = pod.fed, pod.data
    history = {"round": [], "acc": [], "local_loss": [],
               "malicious_weight": [], "participation_rate": [],
               "dropped_fraction": []}
    t0 = time.time()
    for r, params, metrics in pod_rounds(pod, args.rounds, args.seed):
        logits, _ = pod.model.forward_train(
            params, {"images": data.global_x[:400]})
        acc = float((jnp.argmax(logits, -1) == data.global_y[:400]).mean())
        history["round"].append(r + 1)
        history["acc"].append(acc)
        history["local_loss"].append(float(metrics["local_loss"]))
        history["malicious_weight"].append(
            float(metrics["malicious_weight"]))
        history["participation_rate"].append(
            float(metrics["participation_rate"]))
        history["dropped_fraction"].append(
            float(metrics["dropped_fraction"]))
        print(f"round {r + 1}: global_acc={acc:.4f} "
              f"local_loss={float(metrics['local_loss']):.4f} "
              f"mal_w={float(metrics['malicious_weight']):.4f} "
              f"part={float(metrics['participation_rate']):.2f} "
              f"drop={float(metrics['dropped_fraction']):.2f} "
              f"({args.exchange} exchange)", flush=True)
    history["wall_s"] = time.time() - t0
    history["config"] = {"clients": fed.num_users,
                         "aggregator": fed.aggregator,
                         "attack": fed.attack,
                         "malicious": fed.num_malicious,
                         "attack_scale": fed.attack_scale,
                         "participation": fed.participation,
                         "coalition": fed.coalition,
                         "coalition_size": fed.coalition_size,
                         "fault": fed.fault, "fault_rate": fed.fault_rate,
                         "compressor": fed.compressor,
                         "scenario": args.scenario,
                         "exchange": args.exchange}

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out,
                           f"{args.dataset}__{args.exchange}.json"),
              "w") as f:
        json.dump(history, f, indent=1)

    if args.assert_malicious_below is not None:
        final = history["malicious_weight"][-1]
        if not final < args.assert_malicious_below:
            raise SystemExit(
                f"malicious_weight={final:.4f} did not drop below "
                f"{args.assert_malicious_below} after {args.rounds} "
                "rounds")
        print(f"assert ok: malicious_weight={final:.4f} < "
              f"{args.assert_malicious_below}")


def build_population(args: argparse.Namespace, mesh):
    """--population path: cohort engine, [C] axis sharded over ``mesh``.

    The pod path pins one client per device; the population tier
    instead shards the *cohort* stack across the same ``clients`` mesh
    axis via GSPMD (DESIGN.md §11), so N is decoupled from the device
    count. Cross-device reductions are not bitwise-stable, so this path
    is gated on adversary suppression (``--assert-malicious-below``),
    not bit-parity — the unsharded parity matrix lives in
    ``tests/test_population.py``. ``mesh=None`` runs the same cohort
    unsharded on the default device. Returns ``(fed, data, trainer)``.
    """
    import dataclasses as dc

    from repro.config import FedConfig, TrainConfig
    from repro.configs import get_config, scenario_for_population
    from repro.core.engine import PopulationTrainer
    from repro.data import CIFAR_LIKE, MNIST_LIKE
    from repro.data.population import make_synthetic_population

    if args.cohort is None:
        raise SystemExit("--population requires --cohort")
    if mesh is not None and args.cohort % args.clients != 0:
        raise SystemExit(
            f"--cohort {args.cohort} must divide evenly across "
            f"--clients {args.clients} devices for the cohort-axis "
            "sharding")

    passed = dict(num_testers=args.testers, num_malicious=args.malicious,
                  local_steps=args.local_steps,
                  aggregator=args.aggregator,
                  attack=args.attack, attack_kwargs=args.attack_kwargs,
                  attack_scale=args.attack_scale,
                  selector=args.selector,
                  coalition=args.coalition,
                  coalition_size=args.coalition_size,
                  coalition_kwargs=args.coalition_kwargs,
                  fault=args.fault, fault_kwargs=args.fault_kwargs,
                  fault_rate=args.fault_rate,
                  compressor=args.compressor,
                  compressor_kwargs=args.compressor_kwargs,
                  crosstest_impl=args.crosstest_impl,
                  rounds=args.rounds, seed=args.seed)
    passed = {f: v for f, v in passed.items() if v is not None}
    if args.scenario:
        # errors loudly on C > N; coalition membership refits inside
        # the population, so a preset's static member set can never
        # fall outside it
        fed = scenario_for_population(args.scenario, args.population,
                                      args.cohort)
        fed = dc.replace(fed, **passed)
    else:
        base = dict(_FED_CLI_DEFAULTS, num_testers=min(8, args.cohort))
        base.update(passed)
        base.update(num_users=args.population, cohort=args.cohort,
                    participation=(args.cohort / args.population
                                   if args.cohort < args.population
                                   else base.get("participation", 1.0)))
        fed = FedConfig(**base)

    spec = MNIST_LIKE if args.dataset == "mnist_like" else CIFAR_LIKE
    arch = ("fedtest-cnn-mnist" if args.dataset == "mnist_like"
            else "fedtest-cnn")
    cfg = get_config(arch).replace(cnn_channels=(8, 16, 16), cnn_hidden=32)
    from repro.models import build_model
    model = build_model(cfg)
    tc = TrainConfig(optimizer="sgd", lr=args.lr, schedule="constant",
                     batch_size=args.batch, grad_clip=0.0, remat=False)
    # derive-on-gather population data: construction cost independent
    # of N, only the cohort's shards ever exist on device
    data = make_synthetic_population(
        args.population, per_client=max(args.batch * 4, 64),
        image_size=spec.image_size, channels=spec.channels,
        num_classes=spec.num_classes, noise=spec.noise, seed=args.seed)

    trainer = PopulationTrainer(
        model, fed, tc, mesh=mesh, eval_batch=64,
        testers_from_cohort=args.testers_from_cohort)
    return fed, data, trainer


def _run_population(args, mesh):
    import jax

    fed, data, trainer = build_population(args, mesh)
    t0 = time.time()
    state, history = trainer.run(jax.random.PRNGKey(args.seed), data,
                                 verbose=True)
    history["wall_s"] = time.time() - t0
    history["config"] = {"population": args.population,
                         "cohort": args.cohort,
                         "devices": args.clients,
                         "aggregator": fed.aggregator,
                         "attack": fed.attack,
                         "malicious": fed.num_malicious,
                         "attack_scale": fed.attack_scale,
                         "participation": fed.participation,
                         "coalition": fed.coalition,
                         "coalition_size": fed.coalition_size,
                         "compressor": fed.compressor,
                         "scenario": args.scenario}

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out,
                           f"{args.dataset}__population.json"), "w") as f:
        json.dump(history, f, indent=1)

    if args.assert_malicious_below is not None:
        final = history["malicious_weight"][-1]
        if not final < args.assert_malicious_below:
            raise SystemExit(
                f"malicious_weight={final:.4f} did not drop below "
                f"{args.assert_malicious_below} after "
                f"{int(state.round_idx)} rounds")
        print(f"assert ok: malicious_weight={final:.4f} < "
              f"{args.assert_malicious_below}")


if __name__ == "__main__":
    main()
