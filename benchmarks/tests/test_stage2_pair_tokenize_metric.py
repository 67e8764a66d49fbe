"""``stage2_pair_tokenize_ms`` (ISSUE 25), as ``test_span_metrics.py`` checks
its siblings: nothing to read (the parent commit has no such series) gives
``None``, a histogram gives its mean in milliseconds."""

import pytest

from benchmarks import metrics


def test_stage2_pair_tokenize_ms_reads_its_one_series_or_nothing():
    spec = metrics.load("stage2_pair_tokenize_ms")
    (series,) = spec["read"]["series"]
    assert series["labels"] == {"stage": "stage2_pair_tokenize"}
    key = (series["family"], tuple(sorted(series["labels"].items())))
    hists = {key: (4, 0.010)}

    def ctx(hists):
        return {"hist": lambda family, **labels: hists.get((family, tuple(sorted(labels.items()))), (0, 0.0))}

    assert metrics.read(spec, ctx({})) is None
    assert metrics.read(spec, ctx(hists)) == pytest.approx(2.5)
    assert spec["workloads"] == ["rag1m-rerank-closed"]
