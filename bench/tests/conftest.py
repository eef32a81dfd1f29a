"""CPU tests of the benchmark harness.  Four host devices stand in for a
four-chip host; the cells run at a tiny size with the harness's look for
a chip skipped (``harness.run_cell`` is the rest of a run).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import pytest                                                 # noqa: E402

from bench import spec                                        # noqa: E402


# a cell whose files are in place for a later PR, though BENCHMARK.json
# does not list it yet: (configuration, traffic mix, chips)
PENDING = {"iot4k.wave4": ("iot_etl_stats_4k", "wave", 4)}


def _cell(name: str) -> spec.Cell:
    if name not in PENDING:
        return spec.cell(name)
    cfg, mix, chips = PENDING[name]
    return spec.Cell(
        name=name, chips=chips, config_name=cfg,
        config=spec.load_json(os.path.join(spec.BENCH, "configs",
                                           cfg + ".json")),
        traffic_name=mix,
        traffic=spec.load_json(os.path.join(spec.BENCH, "traffic",
                                            mix + ".json")),
        end_to_end=[], per_layer=[])


def tiny_cell(name: str, n_tenants: int = 8, **over) -> spec.Cell:
    """Cell ``name`` with its configuration cut to ``n_tenants`` tenants
    and small capacities (``over`` replaces further sizes or, under the
    key ``traffic``, mix parameters)."""
    base = _cell(name)
    cfg = {**base.config, "params": dict(base.config["params"])}
    traffic = {**base.traffic, "params": dict(base.traffic["params"],
                                              **over.pop("traffic", {}))}
    p = cfg["params"]
    shards = p["n_shards"]
    kinds = p["kinds"]
    rows = sum({"etl": 5, "stats": 2}[kinds[t % len(kinds)]]
               for t in range(n_tenants)) + 2
    p.update(n_tenants=n_tenants, n_streams=-(-rows // shards) * shards,
             batch=16, queue=256)
    p.update(over)
    return spec.Cell(name=name, chips=shards, config_name=base.config_name,
                     config=cfg, traffic_name=base.traffic_name,
                     traffic=traffic, end_to_end=base.end_to_end,
                     per_layer=base.per_layer)


@pytest.fixture
def tiny():
    return tiny_cell
