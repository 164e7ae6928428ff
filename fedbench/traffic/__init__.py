"""Traffic generators, one module per generator.

A workload file names its generator (``"generator": "<module>"``) and
gives its parameters (``"traffic": {...}``). Each module exposes
``make(params, seed) -> dict``: either the stacked client arrays of a
dense federation (``train_x``, ``train_y``, ``train_counts``,
``test_x``, ``test_y``, ``test_counts``, ``global_x``, ``global_y``,
``server_x``, ``server_y``; numpy) or, for a derive-on-gather population,
``{"population": {...}}`` with the provider's parameters and seed.
"""
import importlib


def make(generator: str, params: dict, seed: int) -> dict:
    return importlib.import_module(f"fedbench.traffic.{generator}").make(
        params, seed)
