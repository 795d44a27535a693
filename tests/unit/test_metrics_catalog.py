"""Metric catalog tests (docs/metrics.md).

The catalog (utils/metrics.py) is the single declaration point for every
scalar name any observatory emits: unit, direction, class, description. The
contract that rides on it is ROUTING: SummaryMonitor.add_scalar feeds the
per-host metric ring through the catalog on EVERY rank (before the rank-0
early return), so undeclared names warn exactly once (or raise in strict mode)
and every host's flight-recorder dump carries a mergeable ring.

The drift guard at the bottom runs a REAL engine with a strict-mode store
attached, so any emitter that grows an undeclared scalar name fails here
before it ships.
"""

import json
import logging
import os

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.utils import logger
from deepspeed_tpu.utils.metrics import (DEFAULT_RING_LEN, MetricCatalog,
                                         MetricStore, UnknownMetricError,
                                         default_catalog, export_store,
                                         merge_host_rings, openmetrics_name,
                                         openmetrics_text)
from simple_model import SimpleModel, random_dataset, simple_config

HIDDEN = 16


# ------------------------------------------------------------- resolution


def test_exact_names_resolve():
    cat = default_catalog()
    for name in ("Telemetry/Samples/step_time_ms", "Telemetry/Samples/mfu",
                 "Train/Samples/train_loss", "Train/Samples/loss_scale",
                 "Cluster/step_skew", "Serving/tok_s", "Serving/ttft_ms",
                 "Serving/Fleet/shed", "Serving/Fleet/Goodput/fraction",
                 "Run/Goodput/goodput_fraction",
                 "Pipeline/Goodput/bubble_fraction"):
        spec = cat.resolve(name)
        assert spec is not None, f"{name} undeclared"
        assert spec.unit and spec.description
        assert spec.direction in ("lower_is_better", "higher_is_better",
                                  "neutral")


def test_family_resolution_longest_prefix_wins():
    """Serving/Fleet/Latency/* must shadow the Serving/* catch-all, and an
    exact declaration must beat any family that also matches."""
    cat = default_catalog()
    fleet_p99 = cat.resolve("Serving/Fleet/Latency/ttft_ms_p99")
    assert fleet_p99 is not None
    assert fleet_p99.pattern == "Serving/Fleet/Latency/*"
    assert fleet_p99.direction == "lower_is_better"
    # the catch-all still covers genuinely novel serving scalars
    novel = cat.resolve("Serving/some_future_scalar")
    assert novel is not None and novel.pattern == "Serving/*"
    # exact beats prefix: Serving/tok_s has its own declaration
    assert cat.resolve("Serving/tok_s").pattern == "Serving/tok_s"
    assert cat.resolve("Serving/tok_s").direction == "higher_is_better"


def test_undeclared_name_resolves_none():
    cat = default_catalog()
    assert cat.resolve("Nonsense/made_up") is None
    assert cat.direction("Nonsense/made_up") is None


def test_alerts_family_is_declared():
    """The alert plane's own emissions must route through the same catalog."""
    spec = default_catalog().resolve("Alerts/mfu_drop")
    assert spec is not None and spec.pattern == "Alerts/*"


def test_duplicate_exact_declaration_raises():
    from deepspeed_tpu.utils.metrics import _spec
    dup = [_spec("X/a", "1", "neutral", "test", "one"),
           _spec("X/a", "1", "neutral", "test", "two")]
    with pytest.raises(ValueError, match="duplicate"):
        MetricCatalog(dup)


# ------------------------------------------------------------ metric store


class _Capture(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.records = []

    def emit(self, record):
        self.records.append(record)

    @property
    def text(self):
        return "\n".join(r.getMessage() for r in self.records)


def test_ring_is_bounded_and_ordered():
    store = MetricStore(ring_len=4)
    for step in range(10):
        store.observe("Telemetry/Samples/mfu", 0.1 * step, step)
    series = store.series("Telemetry/Samples/mfu")
    assert len(series) == 4  # fixed geometry: oldest observations evicted
    assert [s for s, _ in series] == [6, 7, 8, 9]
    assert store.last("Telemetry/Samples/mfu") == (9, pytest.approx(0.9))
    assert store.observations == 10  # counts everything ever observed


def test_unknown_metric_warns_exactly_once():
    h = _Capture()
    logger.addHandler(h)
    try:
        store = MetricStore(strict=False)
        store.observe("Bogus/thing", 1.0, 0)
        store.observe("Bogus/thing", 2.0, 1)
        store.observe("Bogus/other", 1.0, 0)
    finally:
        logger.removeHandler(h)
    warnings = [r for r in h.records if "not in the MetricCatalog" in
                r.getMessage()]
    assert len(warnings) == 2  # one per distinct name, not per observation
    # untyped observations are still recorded — warn, don't drop
    assert len(store.series("Bogus/thing")) == 2


def test_strict_store_raises_on_undeclared():
    store = MetricStore(strict=True)
    store.observe("Telemetry/Samples/mfu", 0.5, 0)  # declared: fine
    with pytest.raises(UnknownMetricError, match="Bogus/thing"):
        store.observe("Bogus/thing", 1.0, 0)


def test_ring_len_must_be_positive():
    with pytest.raises(ValueError, match="ring_len"):
        MetricStore(ring_len=0)


def test_monitor_routes_every_rank(tmp_path):
    """The catalog hook in SummaryMonitor.add_scalar runs BEFORE the rank-0
    enabled early-return: a disabled (non-rank-0) monitor still feeds the
    ring, because every host's dump must carry its own metrics."""
    from deepspeed_tpu.utils.monitor import SummaryMonitor
    mon = SummaryMonitor(enabled=False, output_path=str(tmp_path),
                         job_name="m")
    store = MetricStore(ring_len=8, host=3)
    mon.metrics = store
    mon.add_scalar("Telemetry/Samples/mfu", 0.42, 7)
    assert store.last("Telemetry/Samples/mfu") == (7, pytest.approx(0.42))
    # the disabled monitor itself wrote nothing
    assert not os.path.exists(os.path.join(str(tmp_path), "m",
                                           "scalars.jsonl"))


# ------------------------------------------------------------- fleet merge


def _ring(host, ring_len=8, **series):
    store = MetricStore(ring_len=ring_len, host=host)
    for name, obs in series.items():
        for step, value in obs:
            store.observe(name.replace("__", "/"), value, step)
    return store.to_dict()


def test_merge_host_rings_exact_union():
    a = _ring(0, Telemetry__Samples__mfu=[(0, 0.4), (1, 0.41)])
    b = _ring(1, Telemetry__Samples__mfu=[(0, 0.39)],
              Cluster__step_skew=[(1, 1.2)])
    merged = merge_host_rings({0: a, 1: b})
    assert merged["hosts"] == [0, 1] and merged["ring_len"] == 8
    mfu = merged["series"]["Telemetry/Samples/mfu"]
    assert mfu[0] == [[0, 0.4], [1, 0.41]]  # lossless: nothing reduced away
    assert mfu[1] == [[0, 0.39]]
    assert merged["series"]["Cluster/step_skew"] == {1: [[1, 1.2]]}
    # deterministic: same inputs -> byte-identical JSON
    again = merge_host_rings({1: b, 0: a})
    assert json.dumps(merged, sort_keys=True) == json.dumps(again,
                                                            sort_keys=True)


def test_merge_refuses_geometry_mismatch():
    a = _ring(0, ring_len=8, Telemetry__Samples__mfu=[(0, 0.4)])
    b = _ring(1, ring_len=16, Telemetry__Samples__mfu=[(0, 0.4)])
    with pytest.raises(ValueError, match="geometry"):
        merge_host_rings({0: a, 1: b})


# ------------------------------------------------------ OpenMetrics export


def test_openmetrics_name_mangling():
    assert openmetrics_name("Telemetry/Samples/mfu") == "telemetry_samples_mfu"
    assert openmetrics_name("Serving/Fleet/Latency/ttft_ms_p99") == \
        "serving_fleet_latency_ttft_ms_p99"


def test_openmetrics_export_latest_only(tmp_path):
    store = MetricStore(ring_len=8, host=2)
    store.observe("Telemetry/Samples/mfu", 0.40, 1)
    store.observe("Telemetry/Samples/mfu", 0.43, 2)  # only this one exports
    text = openmetrics_text(store.to_dict())
    assert '# TYPE telemetry_samples_mfu gauge' in text
    assert '# UNIT telemetry_samples_mfu' in text
    assert '# HELP telemetry_samples_mfu' in text
    assert 'telemetry_samples_mfu{host="2",step="2"} 0.43' in text
    assert 'step="1"' not in text
    assert text.endswith("# EOF\n")
    path = export_store(store, str(tmp_path / "om" / "metrics.txt"))
    assert open(path).read() == text


# --------------------------------------------------------- catalog drift guard


def _build(**overrides):
    import jax
    model = SimpleModel(HIDDEN)
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
        config_params=simple_config(**overrides))
    return eng


def test_live_emission_paths_stay_in_catalog(tmp_path):
    """Drift guard: a strict-mode store over a real engine run — telemetry,
    memory manifest, numerics and the train loop all emitting — must never
    see an undeclared scalar name. A new emitter that forgets its catalog
    declaration fails HERE, not as a warn-once line in some run log."""
    eng = _build(tensorboard={"enabled": True,
                              "output_path": str(tmp_path),
                              "job_name": "drift"},
                 telemetry={"enabled": True, "peak_tflops": 1e-6,
                            "mfu_window": 4, "output_path": str(tmp_path),
                            "job_name": "drift",
                            "metrics": {"enabled": True,
                                        "strict_catalog": True,
                                        "ring_len": 64}})
    assert eng.telemetry.metric_store is not None
    assert eng.telemetry.metric_store.strict
    xs, ys = _batchpair()
    for _ in range(4):  # raises UnknownMetricError on any undeclared name
        loss = eng(xs, ys)
        eng.backward(loss)
        eng.step()
    eng.telemetry.close()
    store = eng.telemetry.metric_store
    assert store.observations > 0
    assert store.last("Telemetry/Samples/step_time_ms") is not None
    assert store.last("Train/Samples/train_loss") is not None


def _batchpair(n=8, seed=0):
    data = random_dataset(n, HIDDEN, seed=seed)
    return (np.stack([d[0] for d in data]), np.stack([d[1] for d in data]))
