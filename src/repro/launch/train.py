"""Federated training driver (the paper's training kind).

Aggregators, attacks and tester-selection policies are resolved by name
from :mod:`repro.strategies`, so every registered strategy is drivable
from this CLI without touching the engine.

Examples:
  # Fig. 4 reproduction (CIFAR-like, FedTest vs baselines):
  PYTHONPATH=src python -m repro.launch.train --dataset cifar_like \\
      --aggregator fedtest --users 20 --testers 5 --malicious 3 --rounds 60

  # robust baseline vs model-replacement, attackers in the first slots:
  PYTHONPATH=src python -m repro.launch.train --aggregator krum \\
      --attack scaled_update --attack-scale 10 --malicious 4 \\
      --attack-kwargs '{"placement": "first"}'

  # a named scenario preset (see repro.configs.scenarios):
  PYTHONPATH=src python -m repro.launch.train --scenario \\
      krum_vs_scaled_update --rounds 10

  # Federated fine-tuning of an assigned LM backbone (reduced for CPU):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \\
      --dataset lm --rounds 3
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import time
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import FedConfig, TrainConfig, reduce_for_smoke
from repro.configs import (
    get_config, get_scenario, list_scenarios, scenario_for_population)
from repro.core import FederatedTrainer, PopulationTrainer
from repro.data.population import DensePopulationData
from repro.strategies import AGGREGATORS, ATTACKS, COALITIONS, \
    COMPRESSORS, FAULTS, SELECTORS
from repro.checkpoint import CheckpointManager
from repro.data import (
    CIFAR_LIKE, MNIST_LIKE, make_federated_image_dataset, make_token_stream)
from repro.data.partition import build_client_arrays
from repro.data.pipeline import FederatedDataset, split_client_holdout
from repro.models import build_model
from repro.utils.compile_cache import enable_compile_cache


def make_lm_federated_dataset(vocab: int, num_users: int, seq_len: int = 64,
                              seqs_per_user: int = 64, seed: int = 0,
                              skew: float = 0.7) -> FederatedDataset:
    """Non-IID LM data: client i holds ``skew`` of its sequences from its
    own topic and the rest from a uniform topic mix (total disjointness
    would make the global task unlearnable under client drift)."""
    rng = np.random.default_rng(seed)
    toks, topics = make_token_stream(vocab, num_users * seqs_per_user * 2,
                                     seq_len + 1, num_topics=num_users,
                                     seed=seed)
    x = toks[:, :-1]
    y = toks[:, 1:]
    n = num_users * seqs_per_user
    by_topic = [list(np.flatnonzero(topics[:n] == t)) for t in
                range(num_users)]
    pool = list(range(n))
    rng.shuffle(pool)
    parts = []
    used = set()
    for u in range(num_users):
        own = [i for i in by_topic[u % num_users] if i not in used]
        take_own = int(seqs_per_user * skew)
        sel = own[:take_own]
        used.update(sel)
        fill = [i for i in pool if i not in used][:seqs_per_user - len(sel)]
        used.update(fill)
        parts.append(np.array(sel + fill, dtype=np.int64))
    xs, ys, counts = build_client_arrays(x[:n], y[:n], parts)
    train, test = split_client_holdout(xs, ys, counts, frac=0.25)
    return FederatedDataset(train=train, test=test,
                            global_x=jnp.asarray(x[n:n + 512]),
                            global_y=jnp.asarray(y[n:n + 512]),
                            server_x=jnp.asarray(x[n + 512:n + 768]),
                            server_y=jnp.asarray(y[n + 512:n + 768]))


# FedConfig fields the CLI leaves unset use these (the argparse flags
# default to None so --scenario can tell "explicitly passed" apart)
_FED_CLI_DEFAULTS = dict(
    num_users=20, num_testers=5, num_malicious=0, rounds=40,
    local_steps=10, score_power=4.0, score_decay=0.5,
    aggregator="fedtest", aggregator_kwargs={},
    attack="random_weights", attack_kwargs={}, attack_scale=1.0,
    selector="rotating", selector_kwargs={},
    coalition="none", coalition_kwargs={}, coalition_size=0,
    fault="none", fault_kwargs={}, fault_rate=0.1,
    compressor="identity", compressor_kwargs={}, seed=0)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="fedtest-cnn")
    ap.add_argument("--smoke", action="store_true",
                    help="reduce the arch for CPU-scale runs")
    ap.add_argument("--dataset", default="cifar_like",
                    choices=["cifar_like", "mnist_like", "lm"])
    ap.add_argument("--scenario", default=None, choices=list_scenarios(),
                    help="named FedConfig preset; flags set explicitly "
                         "on the CLI override preset fields")
    ap.add_argument("--aggregator", default=None,
                    choices=list(AGGREGATORS.names()))
    ap.add_argument("--agg-kwargs", default=None, type=json.loads,
                    help="JSON kwargs for the aggregator ctor")
    ap.add_argument("--users", type=int, default=None)
    ap.add_argument("--population", type=int, default=None,
                    help="run the population tier (DESIGN.md §11) over "
                         "this many clients: per-round compute touches "
                         "only the sampled cohort (--cohort), scores "
                         "stay dense [N]. Scenario presets are refit "
                         "via scenario_for_population")
    ap.add_argument("--cohort", type=int, default=None,
                    help="cohort slot capacity C for --population "
                         "(default: the whole population); the "
                         "Bernoulli sampling rate is refit to C/N. "
                         "Errors loudly when C > N")
    ap.add_argument("--testers-from-cohort", action="store_true",
                    help="population tier: recruit the round's testing "
                         "committee from the sampled cohort (at C << N "
                         "a population-wide tester almost never "
                         "participates and scoring degenerates; "
                         "DESIGN.md §11)")
    ap.add_argument("--testers", type=int, default=None)
    ap.add_argument("--malicious", type=int, default=None)
    ap.add_argument("--attack", default=None,
                    choices=list(ATTACKS.names()))
    ap.add_argument("--attack-kwargs", default=None, type=json.loads,
                    help="JSON kwargs for the attack ctor, e.g. "
                         '\'{"placement": "first"}\'')
    ap.add_argument("--attack-scale", type=float, default=None)
    ap.add_argument("--selector", default=None,
                    choices=list(SELECTORS.names()))
    ap.add_argument("--selector-kwargs", default=None, type=json.loads)
    ap.add_argument("--coalition", default=None,
                    choices=list(COALITIONS.names()),
                    help="coordinated multi-client adversary "
                         "(repro.strategies.COALITIONS; DESIGN.md §7); "
                         "size via --coalition-size")
    ap.add_argument("--coalition-size", type=int, default=None,
                    help="number of coordinated members (placement via "
                         "--coalition-kwargs)")
    ap.add_argument("--coalition-kwargs", default=None, type=json.loads,
                    help="JSON kwargs for the coalition ctor, e.g. "
                         '\'{"boost_to": 0.9, "deflate_top": 2}\'')
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--rounds-per-call", type=int, default=1,
                    help=">1 routes steady-state training through the "
                         "scanned multi-round driver (lax.scan over this "
                         "many rounds per dispatch, donated state "
                         "buffers); global accuracy is evaluated at "
                         "chunk boundaries")
    ap.add_argument("--local-steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--score-power", type=float, default=None)
    ap.add_argument("--score-decay", type=float, default=None)
    ap.add_argument("--samples", type=int, default=20000)
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--fault", default=None, choices=list(FAULTS.names()),
                    help="availability fault injected after tester "
                         "selection (repro.strategies.FAULTS; "
                         "DESIGN.md §9)")
    ap.add_argument("--fault-rate", type=float, default=None,
                    help="per-round drop probability offered to the "
                         "fault model (dropout)")
    ap.add_argument("--fault-kwargs", default=None, type=json.loads,
                    help="JSON kwargs for the fault ctor, e.g. "
                         '\'{"placement": "first", "size": 2}\'')
    ap.add_argument("--compressor", default=None,
                    choices=list(COMPRESSORS.names()),
                    help="compressed update exchange "
                         "(repro.strategies.COMPRESSORS; DESIGN.md §12):"
                         " clients transmit encoded deltas with "
                         "per-client error feedback instead of dense "
                         "models")
    ap.add_argument("--compressor-kwargs", default=None, type=json.loads,
                    help="JSON kwargs for the compressor ctor, e.g. "
                         '\'{"k": 0.05}\' (topk) or \'{"chunk": 256}\' '
                         "(int8)")
    ap.add_argument("--assert-malicious-below", type=float, default=None,
                    help="exit non-zero unless the final round's "
                         "malicious_weight is below this bar (the CI "
                         "dropout-suppression gate)")
    ap.add_argument("--crosstest-impl", default=None,
                    choices=["batched", "reference"],
                    help="cross-testing dispatch model (DESIGN.md §10): "
                         "one fused [N, batch] eval per tester vs the "
                         "per-client reference loop (bit-identical)")
    ap.add_argument("--eval-resample-every", type=int, default=0,
                    help="resample the schedule-keyed tester eval "
                         "batches every N rounds (0 = fixed prefix "
                         "slice, the legacy behaviour)")
    ap.add_argument("--out", default="experiments/train")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (final state is always "
                         "saved there; periodic saves via --ckpt-every)")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save the full round state every N completed "
                         "rounds (0 = final save only)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest checkpoint from --ckpt-dir "
                         "and continue to --rounds; refuses a manifest "
                         "mismatch")
    return ap


class Run(NamedTuple):
    """What :func:`build_run` makes from the parsed flags."""

    cfg: Any            # ModelConfig
    model: Any          # repro.models.Model
    fed: FedConfig
    train: TrainConfig
    data: Any           # FederatedDataset | DensePopulationData
    trainer: FederatedTrainer


def build_run(args: argparse.Namespace) -> Run:
    """Model, configs, client data and trainer for the parsed flags."""
    cfg = get_config(args.arch)
    if args.dataset == "mnist_like" and args.arch == "fedtest-cnn":
        cfg = get_config("fedtest-cnn-mnist")
    if args.smoke:
        cfg = reduce_for_smoke(cfg).replace(dtype="float32")
    model = build_model(cfg)

    passed = dict(num_users=args.users, num_testers=args.testers,
                  num_malicious=args.malicious, rounds=args.rounds,
                  local_steps=args.local_steps,
                  score_power=args.score_power,
                  score_decay=args.score_decay,
                  aggregator=args.aggregator,
                  aggregator_kwargs=args.agg_kwargs,
                  attack=args.attack, attack_kwargs=args.attack_kwargs,
                  attack_scale=args.attack_scale,
                  selector=args.selector,
                  selector_kwargs=args.selector_kwargs,
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
    if args.cohort is not None and args.population is None:
        raise SystemExit("--cohort requires --population")
    if args.population is not None:
        # population tier (DESIGN.md §11): N comes from --population,
        # the sampling rate from the cohort budget
        if args.users is not None:
            raise SystemExit("--population replaces --users; pass one")
        if args.eval_resample_every:
            raise SystemExit("--eval-resample-every is a dense-driver "
                             "feature; the population tier gathers "
                             "tester rows directly")
        cohort = args.cohort or args.population
        if args.scenario:
            # scenario_for_population errors loudly on C > N and refits
            # coalition membership inside the population
            fed = scenario_for_population(args.scenario, args.population,
                                          cohort)
            fed = dataclasses.replace(
                fed, **{f: v for f, v in passed.items()
                        if f != "num_users"})
        else:
            base = {**_FED_CLI_DEFAULTS, **passed,
                    "num_users": args.population, "cohort": cohort}
            if cohort < args.population:
                base["participation"] = cohort / args.population
            fed = FedConfig(**base)
    elif args.scenario:
        # preset first; every explicitly-passed flag overrides it
        fed = dataclasses.replace(get_scenario(args.scenario), **passed)
    else:
        fed = FedConfig(**{**_FED_CLI_DEFAULTS, **passed})
    tc = TrainConfig(optimizer=args.optimizer, lr=args.lr,
                     schedule="constant", batch_size=args.batch,
                     grad_clip=0.0, remat=False)

    if args.dataset == "lm":
        data = make_lm_federated_dataset(cfg.vocab_size, fed.num_users,
                                         seed=fed.seed)
    else:
        spec = CIFAR_LIKE if args.dataset == "cifar_like" else MNIST_LIKE
        data = make_federated_image_dataset(spec, fed.num_users,
                                            num_samples=args.samples,
                                            seed=fed.seed)

    if args.population is not None:
        data = DensePopulationData(data)
        trainer = PopulationTrainer(
            model, fed, tc, rounds_per_call=args.rounds_per_call,
            testers_from_cohort=args.testers_from_cohort)
    else:
        trainer = FederatedTrainer(
            model, fed, tc, rounds_per_call=args.rounds_per_call,
            eval_resample_every=args.eval_resample_every)
    return Run(cfg, model, fed, tc, data, trainer)


def main(argv: Optional[Sequence[str]] = None):
    enable_compile_cache()
    args = build_parser().parse_args(argv)
    cfg, model, fed, tc, data, trainer = build_run(args)

    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir,
                                save_every=args.ckpt_every)
    init_state = None
    if args.resume:
        if mgr is None:
            raise SystemExit("--resume requires --ckpt-dir")
        init_state, at = trainer.restore_checkpoint(mgr)
        print(f"resuming from round {at} in {args.ckpt_dir}")

    # SIGTERM drains the loop at the next driver-call boundary; the
    # state returned by run() is then saved below like any other exit,
    # so an orchestrator's soft kill never loses completed rounds.
    stop = {"flag": False}

    def _on_sigterm(signum, frame):
        stop["flag"] = True
        print("SIGTERM: finishing current chunk, then checkpointing")

    prev_handler = signal.signal(signal.SIGTERM, _on_sigterm)

    t0 = time.time()
    state, history = trainer.run(jax.random.PRNGKey(fed.seed), data,
                                 verbose=True, state=init_state,
                                 ckpt=mgr,
                                 should_stop=lambda: stop["flag"])
    signal.signal(signal.SIGTERM, prev_handler)

    completed = int(state.round_idx)   # NOT fed.rounds: the run may have
    if mgr is not None:                # stopped early (SIGTERM/resume)
        trainer.save_checkpoint(mgr, state, step=completed)
        print(f"checkpoint saved at round {completed} -> {args.ckpt_dir}")
    if stop["flag"]:
        raise SystemExit(f"interrupted at round {completed} (state saved)")

    history["wall_s"] = time.time() - t0
    history["config"] = {"arch": cfg.name, "dataset": args.dataset,
                         "aggregator": fed.aggregator,
                         "attack": fed.attack, "selector": fed.selector,
                         "coalition": fed.coalition,
                         "coalition_size": fed.coalition_size,
                         "fault": fed.fault, "fault_rate": fed.fault_rate,
                         "compressor": fed.compressor,
                         "scenario": args.scenario,
                         "users": fed.num_users, "testers": fed.num_testers,
                         "malicious": fed.num_malicious,
                         "cohort": fed.cohort,
                         "resumed": bool(args.resume)}

    os.makedirs(args.out, exist_ok=True)
    tag = (f"{cfg.name}__{args.dataset}__{fed.aggregator}"
           f"__{fed.attack}__m{fed.num_malicious}")
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(history, f, indent=1)
    if history["global_accuracy"]:
        print(f"final accuracy: {history['global_accuracy'][-1]:.4f} "
              f"({history['wall_s']:.0f}s) -> {args.out}/{tag}.json")
    else:   # resumed past the target: nothing ran, nothing to report
        print(f"no rounds to run (already at {completed}/{fed.rounds})")

    if args.assert_malicious_below is not None:
        final = history["malicious_weight"][-1]
        if not final < args.assert_malicious_below:
            raise SystemExit(
                f"malicious_weight={final:.4f} did not drop below "
                f"{args.assert_malicious_below} after {completed} "
                "rounds")
        print(f"assert ok: malicious_weight={final:.4f} < "
              f"{args.assert_malicious_below}")


if __name__ == "__main__":
    main()
