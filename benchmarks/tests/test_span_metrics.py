"""The python readers added with the span metrics, on hand-made ``ctx``:
each returns ``None`` where the program has nothing to read (as the parent
commit has not), and the share it is named for where it has."""

import pytest

from benchmarks import metrics


def _ctx(hists):
    return {"hist": lambda family, **labels: hists.get((family, tuple(sorted(labels.items()))), (0, 0.0))}


def _stage(family, stage):
    return (family, (("stage", stage),))


WALL, CPU = "pathway_serve_stage_seconds", "pathway_serve_stage_cpu_seconds"


def test_serve_host_oncpu_share_sums_cpu_over_wall_of_the_leaf_brackets():
    spec = metrics.load("serve_host_oncpu_share")
    assert metrics.read(spec, _ctx({})) is None
    hists = {
        # the twin is sampled: 2 of 10 brackets measured, 0.8 ms of CPU each
        _stage(WALL, "stage1_tokenize"): (10, 0.010), _stage(CPU, "stage1_tokenize"): (2, 0.0016),
        _stage(WALL, "stage2_packrows"): (5, 0.030), _stage(CPU, "stage2_packrows"): (5, 0.012),
        # launch holds tokenize and dispatch: not a leaf, never summed
        _stage(WALL, "launch"): (10, 1.0), _stage(CPU, "launch"): (10, 1.0),
        # a wall series whose CPU twin the program does not have is skipped
        _stage(WALL, "stage1_dispatch"): (10, 0.5),
    }
    assert metrics.read(spec, _ctx(hists)) == pytest.approx(100.0 * 0.020 / 0.040)


def test_ingest_embed_oncpu_share():
    spec = metrics.load("ingest_embed_oncpu_share")
    wall = ("pathway_freshness_stage_seconds", (("stage", "embed"),))
    cpu = ("pathway_freshness_stage_cpu_seconds", (("stage", "embed"),))
    assert metrics.read(spec, _ctx({})) is None
    assert metrics.read(spec, _ctx({wall: (3, 0.048)})) is None  # the parent: no CPU twin
    assert metrics.read(spec, _ctx({wall: (3, 0.048), cpu: (1, 0.016 / 3)})) == pytest.approx(100.0 / 3.0)


def test_dispatcher_busy_share_reads_the_phase_counters():
    from pathway_tpu import observe

    spec = metrics.load("dispatcher_busy_share")
    phases = {p: observe.counter("pathway_serve_dispatcher_seconds_total", phase=p) for p in ("idle", "window", "launch", "advance")}
    for c in phases.values():
        c.reset()
    assert metrics.read(spec, {}) is None  # nothing counted (or a program without the counter)
    for phase, seconds in (("idle", 1.0), ("window", 5.0), ("launch", 3.0), ("advance", 1.0)):
        phases[phase].inc(seconds)
    try:
        assert metrics.read(spec, {}) == pytest.approx(40.0)
    finally:
        for c in phases.values():
            c.reset()


@pytest.mark.parametrize("name", [
    "admission_wait_mean_ms", "sched_launch_ms", "ticket_wake_mean_ms", "stage1_tokenize_ms", "stage1_lock_wait_ms",
    "stage1_dispatch_ms", "stage1_postprocess_ms", "stage2_gather_ms", "stage2_packrows_ms", "stage2_dispatch_ms",
    "stage2_postprocess_ms", "ivf_absorb_commit_ms",
])
def test_histogram_metrics_read_their_one_series_or_nothing(name):
    spec = metrics.load(name)
    (series,) = spec["read"]["series"]
    key = (series["family"], tuple(sorted(series.get("labels", {}).items())))
    assert metrics.read(spec, _ctx({})) is None
    assert metrics.read(spec, _ctx({key: (4, 0.010)})) == pytest.approx(2.5)
