"""One FedTest round (Algorithm 1 of the paper), written plainly.

Per round, from the run key and the round index: the K testers (a
uniform K-subset), the Bernoulli participation draw and its cohort (the
first C sampled clients in index order), and each client's batch rows.
Then each participating client runs ``local_steps`` SGD steps from the
global model; the attackers replace their trained model with Gaussian
weights of the same per-leaf spread; every tester measures every model's
accuracy on its own rows (a client outside the cohort is seen as the
global model); the scores are a moving average of the testers' mean
accuracy raised to ``score_power`` (to 1 in the first
``power_warmup_rounds`` rounds); the weights are the normalised scores of
the participants, and the new global model is the weighted sum of the
participants' models.

The random draws follow the round schedule the federation uses (the
same ``jax.random`` streams of the run key), so the program and this
reference train on the same rows and attack with the same noise.
Everything else is computed here, from the benchmark's own weights and
data, clients one at a time.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedbench.reference import F32, Precision, model as family_module


class Keys(NamedTuple):
    batch: Any
    attack: Any
    test: Any
    part: Any


def round_keys(run_key, r: int) -> Keys:
    base = jax.random.fold_in(run_key, r)
    k_batch, k_attack, k_test, _ = jax.random.split(base, 4)
    return Keys(k_batch, k_attack, k_test, jax.random.fold_in(base, 6))


def testers(key, n: int, k: int, r: int) -> np.ndarray:
    u = jax.random.uniform(jax.random.fold_in(key, r), (n,))
    return np.asarray(jax.lax.top_k(u, k)[1])


def cohort(part_key, n: int, participation: float, capacity: int):
    """(sampled client ids in index order, first ``capacity`` of them)."""
    if participation >= 1.0:
        ids = np.arange(n)
        return ids, ids
    bern = np.asarray(jax.random.bernoulli(part_key, participation, (n,)))
    ids = np.flatnonzero(bern) if bern.any() else np.arange(n)
    return ids, ids[:capacity]


class Population:
    """The derive-on-gather population: class prototypes plus Gaussian
    noise, client ``i``'s train rows from ``fold_in(train stream, i)`` and
    its tester rows from ``fold_in(test stream, i)``."""

    def __init__(self, p: dict):
        key = jax.random.PRNGKey(p["seed"])
        k_proto, self.key = jax.random.split(key)
        self.protos = jax.random.normal(
            k_proto, (p["num_classes"], p["image_size"], p["image_size"],
                      p["channels"]))
        self.noise = p["noise"]
        self.per_client = p["per_client"]
        self.num_clients = p["num_clients"]

    def shard(self, stream: int, client, rows: int):
        key = jax.random.fold_in(jax.random.fold_in(self.key, stream),
                                 client)
        ky, kn = jax.random.split(key)
        labels = jax.random.randint(ky, (rows,), 0, self.protos.shape[0])
        x = self.protos[labels] + self.noise * jax.random.normal(
            kn, (rows,) + self.protos.shape[1:])
        return x, labels

    @property
    def counts(self):
        return np.full((self.num_clients,), self.per_client, np.int32)


class Dense:
    """A stacked dense federation (the traffic generator's arrays)."""

    def __init__(self, arrays: dict):
        self.a = arrays
        self.counts = arrays["train_counts"]
        self.num_clients = len(self.counts)

    def train_batches(self, ids, rows):
        """``rows [G, steps, batch]`` of each client's train shard."""
        ids = np.asarray(ids)[:, None, None]
        return (jnp.asarray(self.a["train_x"][ids, rows]),
                jnp.asarray(self.a["train_y"][ids, rows]))

    def test_rows(self, c: int, rows: int):
        return (jnp.asarray(self.a["test_x"][c][:rows]),
                jnp.asarray(self.a["test_y"][c][:rows]))


class Population_(Population):
    def train_batches(self, ids, rows):
        """``rows [G, steps, batch]`` of each client's train shard."""
        x, y = jax.vmap(lambda c: self.shard(0, c, self.per_client))(
            jnp.asarray(ids))
        take = jax.vmap(lambda a, i: a[i])
        return take(x, jnp.asarray(rows)), take(y, jnp.asarray(rows))

    def test_rows(self, c: int, rows: int):
        return self.shard(1, c, rows)


def data_source(traffic: dict):
    if "population" in traffic:
        return Population_(traffic["population"])
    return Dense(traffic)


class Round:
    """The reference round for one cell: its model family, configuration,
    federation and precision. ``fault`` plants one of the check's faults
    (``half_batch``, ``altered_report``, ``no_exchange``) for
    ``calibrate.py``."""

    def __init__(self, cfg: dict, fed: dict, *, prec: Precision = F32,
                 fault: Optional[str] = None, eval_chunk: int = 0,
                 group: int = 1, exchange_parts: int = 1):
        self.cfg, self.fed, self.prec, self.fault = cfg, fed, prec, fault
        self.mod = family_module(cfg["family"])
        self.eval_chunk = eval_chunk
        self.group = group
        self.exchange_parts = exchange_parts
        # ``group`` clients at a time, each on its own
        self._train = jax.jit(jax.vmap(self._train_impl,
                                       in_axes=(None, 0, 0)))
        self._correct = jax.jit(jax.vmap(self._correct_impl,
                                         in_axes=(0, None, None)))

    # ----------------------------------------------------------- pieces
    def _ctx(self):
        return jax.default_matmul_precision("highest")

    def _loss(self, params, x, y):
        logits = self.mod.logits(params, self.cfg, {"x": x}, self.prec)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    def _train_impl(self, params, bx, by):
        lr, store = self.fed["lr"], self.prec.store

        def step(p, xy):
            loss, g = jax.value_and_grad(self._loss)(p, *xy)
            p = jax.tree_util.tree_map(
                lambda w, d: store(w.astype(jnp.float32)
                                   - lr * d.astype(jnp.float32)), p, g)
            return p, loss

        params, losses = jax.lax.scan(step, params, (bx, by))
        return params, jnp.mean(losses)

    def _correct_impl(self, params, x, y):
        logits = self.mod.logits(params, self.cfg, {"x": x}, self.prec)
        hit = (jnp.argmax(logits, -1) == y).astype(jnp.int32)
        return hit.reshape(hit.shape[0], -1).sum(-1)

    def accuracies(self, stacked, x, y) -> np.ndarray:
        """``[G, K]`` accuracy of each of a group of models (leaves
        ``[G, ...]``) on each tester's rows (``x [K, rows, ...]``),
        evaluated in chunks of ``eval_chunk`` rows."""
        k, rows = x.shape[:2]
        fx = x.reshape((k * rows,) + x.shape[2:])
        fy = y.reshape((k * rows,) + y.shape[2:])
        step = self.eval_chunk or k * rows
        with self._ctx():
            hits = [np.asarray(self._correct(stacked, fx[i:i + step],
                                             fy[i:i + step]))
                    for i in range(0, k * rows, step)]
        hits = np.concatenate(hits, axis=1)
        return hits.reshape(-1, k, rows).sum(2) / float(y[0].size)

    def attack(self, key, trained):
        leaves, tree = jax.tree_util.tree_flatten(trained)
        ks = jax.random.split(key, len(leaves))
        scale = self.fed.get("attack_scale", 1.0)
        bad = [self.prec.store(
            jax.random.normal(k, l.shape, jnp.float32)
            * (jnp.std(l.astype(jnp.float32)) + 1e-6) * scale)
            for k, l in zip(ks, leaves)]
        return jax.tree_util.tree_unflatten(tree, bad)

    # ------------------------------------------------------------ round
    def run(self, g, scores: Dict[str, Any], r: int, run_key, data
            ) -> tuple:
        """One round from global weights ``g`` and the score state
        ``{"scores": [N], "rounds_seen": int}``. Returns the new global
        weights, the new score state and the round's metrics."""
        fed = self.fed
        n, k_num = data.num_clients, fed["num_testers"]
        steps, batch = fed["local_steps"], fed["batch_size"]
        keys = round_keys(run_key, r)
        tester_ids = testers(keys.test, n, k_num, r)
        sampled, members = cohort(keys.part, n, fed["participation"],
                                  fed.get("cohort") or n)
        if fed.get("testers_from_cohort"):
            tester_ids = members[tester_ids % max(len(members), 1)]
        part = np.zeros((n,), np.float32)
        part[members] = 1.0
        u = jax.random.uniform(keys.batch, (n, steps, batch))
        bidx = np.asarray((u * jnp.asarray(data.counts)[:, None, None])
                          .astype(jnp.int32))
        if self.fault == "half_batch":
            bidx = bidx[:, :, :batch // 2]
        malicious = set(fed["malicious"])

        # local training and the attack, ``group`` clients at a time
        groups, losses = [], []
        for i in range(0, len(members), self.group):
            ids = members[i:i + self.group]
            # a short last group repeats its first client, so every group
            # runs one compiled shape; the repeats are dropped below
            padded = np.concatenate(
                [ids, np.full(min(self.group, len(members)) - len(ids),
                              ids[0])])
            x, y = data.train_batches(padded, bidx[padded])
            with self._ctx():
                stacked, loss = self._train(g, x, y)
            loss = np.asarray(loss)[:len(ids)]
            for j, c in enumerate(ids):
                if int(c) in malicious:
                    one = jax.tree_util.tree_map(lambda l: l[j], stacked)
                    bad = self.attack(
                        jax.random.fold_in(keys.attack, int(c)), one)
                    stacked = jax.tree_util.tree_map(
                        lambda l, b: l.at[j].set(b), stacked, bad)
            groups.append((ids, stacked))
            losses.extend(loss.tolist())

        rows = fed["eval_batch"]
        tdata = [data.test_rows(int(t), rows) for t in tester_ids]
        tx = jnp.stack([x for x, _ in tdata])
        ty = jnp.stack([y for _, y in tdata])
        acc = np.empty((k_num, n), np.float64)
        if len(members) < n:
            one = jax.tree_util.tree_map(lambda l: l[None], g)
            acc[:] = self.accuracies(one, tx, ty)[0][:, None]
        for ids, stacked in groups:
            acc[:, ids] = self.accuracies(stacked, tx, ty)[:len(ids)].T

        if self.fault == "altered_report":
            # the first tester's report replaced by uniform accuracies
            acc[0] = np.random.default_rng(r).uniform(size=n)
        report = part[tester_ids]
        combined = (report @ acc) / max(report.sum(), 1e-9)
        seen = scores["rounds_seen"]
        power = 1.0 if seen < fed["power_warmup_rounds"] else \
            fed["score_power"]
        powered = np.clip(combined, 0.0, 1.0) ** power
        new = powered if seen == 0 else (
            fed["score_decay"] * scores["scores"]
            + (1.0 - fed["score_decay"]) * powered)
        new = np.where(part > 0, new, scores["scores"])
        total = new.clip(0).sum()
        w = new.clip(0) / total if total > 1e-12 else np.full(n, 1.0 / n)
        w = w * part
        w = w / w.sum() if w.sum() > 1e-12 else part / part.sum()

        summed = w.copy()
        if self.fault == "no_exchange":
            # the first device's slots of the cohort, never reduced
            capacity = fed.get("cohort") or n
            summed[members[capacity // self.exchange_parts:]] = 0.0
        new_g = None
        for ids, stacked in groups:
            wg = np.zeros(jax.tree_util.tree_leaves(stacked)[0].shape[0],
                          np.float32)
            wg[:len(ids)] = summed[ids]
            part_sum = jax.tree_util.tree_map(
                lambda l: jnp.tensordot(wg, l.astype(jnp.float32), 1),
                stacked)
            new_g = part_sum if new_g is None else jax.tree_util.tree_map(
                jnp.add, new_g, part_sum)
        new_g = jax.tree_util.tree_map(self.prec.store, new_g)
        mal = np.zeros((n,), np.float32)
        mal[list(malicious)] = 1.0
        metrics = {"local_loss": float(np.mean(losses)),
                   "weights": w, "scores": new,
                   "malicious_weight": float(w @ mal),
                   "sampled": len(sampled)}
        return new_g, {"scores": new, "rounds_seen": seen + 1}, metrics
