"""A derive-on-gather client population (cross-device traffic).

The client shards stay the program's own
(``repro.data.population.make_synthetic_population``): their gather is
part of the round's work, so the harness builds the provider from these
parameters and the seed, and the reference derives the same shards with
its own code (``fedbench/reference/fedtest.py``).
"""


def make(params: dict, seed: int) -> dict:
    return {"population": dict(params, seed=seed)}
