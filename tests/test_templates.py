"""L7 template tests: the YAML loader's object construction and the
adaptive-RAG template served end-to-end through the CLI
(reference: docs/2.developers/7.templates/.adaptive-rag/article.py)."""

from __future__ import annotations

import io
import json
import os

import pytest

import chip_smoke

from .utils import REPO_ROOT


def test_yaml_loader_variables_inside_constructors():
    from pathway_tpu.internals.yaml_loader import load_yaml

    cfg = load_yaml(
        io.StringIO(
            """
$dim: 24
shared: &enc !pw.xpacks.llm.embedders.TpuEmbedder
  dimension: $dim
  n_layers: 1
  max_length: 32
again: *enc
number: $dim
"""
        )
    )
    assert cfg["number"] == 24
    assert cfg["shared"].get_embedding_dimension() == 24
    assert cfg["again"] is cfg["shared"], "anchor must share one instance"


def test_yaml_loader_resolves_nested_modules():
    from pathway_tpu.internals.yaml_loader import _resolve_callable

    assert _resolve_callable(
        "pw.xpacks.llm.question_answering.AdaptiveRAGQuestionAnswerer"
    ).__name__ == "AdaptiveRAGQuestionAnswerer"
    assert _resolve_callable("pw.stdlib.indexing.BruteForceKnnFactory")


def _template_server(yaml_path, probe=None):
    """The launcher as chip_smoke.py starts it, pinned to the CPU."""
    return chip_smoke.TemplateServer(
        yaml_path, {**os.environ, "JAX_PLATFORMS": "cpu"}, probe=probe
    )


@pytest.mark.slow
def test_adaptive_rag_template_serves_end_to_end():
    """python -m pathway_tpu.cli run templates/adaptive_rag.yaml answers a
    query end-to-end, and says at start-up which platform it runs on."""
    with _template_server("templates/adaptive_rag.yaml") as server:
        docs = server.post("/v1/retrieve", {"query": "anything", "k": 3}, 60)
        assert len(docs) == 3
        assert all("text" in d and "metadata" in d for d in docs)
        paths = {d["metadata"]["path"] for d in docs}
        assert any("sample_documents" in p for p in paths)

        answer = server.post("/v1/pw_ai_answer", {"prompt": "What do cats do?"})
        assert isinstance(answer, str) and answer.strip(), answer
    assert "platform=cpu " in server.output()


def test_yaml_loader_circular_variables_raise():
    import io as _io

    import pytest as _pytest

    from pathway_tpu.internals.yaml_loader import load_yaml

    with _pytest.raises(ValueError, match="circular"):
        load_yaml(_io.StringIO("$a: $b\n$b: $a\nx: $a\n"))


# ---------------------------------------------------------------------------
# template FLEET (VERDICT r4 #1): each app launched by `pathway-tpu run`
# and answering a real query end-to-end
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_demo_question_answering_template_serves_end_to_end():
    """Reference demo-question-answering app shape
    (docs/2.developers/7.templates/1000.demo-question-answering.md):
    retrieve + statistics + list_documents + answer over one YAML app."""
    with _template_server("templates/demo_question_answering.yaml") as server:
        docs = server.post("/v1/retrieve", {"query": "anything", "k": 3}, 60)
        assert len(docs) == 3 and all("text" in d for d in docs)
        stats = server.post("/v1/statistics", {}, 60)
        assert stats["file_count"] >= 3, stats
        listed = server.post("/v1/pw_list_documents", {}, 60)
        assert {d["path"].rsplit("/", 1)[-1] for d in listed} >= {
            "animals.txt", "dataflow.txt", "tpu.txt"
        }
        answer = server.post("/v1/pw_ai_answer", {"prompt": "What do cats do?"})
        assert isinstance(answer, str) and answer.strip()
        summary = server.post(
            "/v1/pw_ai_summary", {"text_list": ["cats purr", "dogs bark"]}
        )
        assert isinstance(summary, str) and summary.strip()


@pytest.mark.slow
def test_multimodal_rag_template_serves_images():
    """Reference multimodal-rag shape (1003.template-multimodal-rag.md):
    images become searchable documents via local CLIP labels."""
    with _template_server(
        "templates/multimodal_rag.yaml", probe={"query": "red", "k": 1}
    ) as server:
        docs = server.post("/v1/retrieve", {"query": "red square", "k": 3}, 60)
        assert len(docs) == 3
        # every indexed image chunk carries CLIP labels as searchable text
        assert all(d["text"] for d in docs), docs
        paths = {d["metadata"]["path"].rsplit("/", 1)[-1] for d in docs}
        assert paths == {
            "red_square.png", "blue_circle.png", "green_stripes.png"
        }, paths
        answer = server.post(
            "/v1/pw_ai_answer", {"prompt": "Which image shows a red square?"}
        )
        assert isinstance(answer, str) and answer.strip()


@pytest.mark.slow
def test_slides_search_template_returns_slides():
    """Reference slides-search shape (1010.template-slides-search.md):
    the deck is parsed per slide and /v1/pw_ai_answer returns SLIDES."""
    with _template_server(
        "templates/slides_search.yaml", probe={"query": "revenue", "k": 1}
    ) as server:
        slides = server.post("/v1/pw_ai_answer", {"prompt": "revenue growth"}, 120)
        assert isinstance(slides, list) and slides, slides
        assert all("text" in s and "metadata" in s for s in slides)
        assert all("slide" in s["metadata"] for s in slides), slides
        # three slides indexed from one deck
        stats = server.post("/v1/statistics", {}, 60)
        assert stats["file_count"] == 3, stats


def test_kafka_etl_template_unifies_time_zones(monkeypatch):
    """Reference kafka-etl shape (140.kafka-etl.md): two topics with
    different time zones unify into one epoch-stamped stream, loaded back
    to kafka — driven end-to-end over the fake client."""
    import sys as _sys
    import types as _types

    sent = []

    class Msg:
        def __init__(self, partition, offset, value):
            self.partition = partition
            self.offset = offset
            self.value = value

    topics = {
        "timezone1": [
            Msg(0, 0, json.dumps({
                "date": "2024-02-05 10:01:52.884548 -0500",
                "message": "NYC event",
            }).encode()),
        ],
        "timezone2": [
            Msg(0, 0, json.dumps({
                "date": "2024-02-05 16:01:52.884548 +0100",
                "message": "Paris event",
            }).encode()),
        ],
    }

    class FakeConsumer:
        def __init__(self, topic, **kw):
            self._msgs = topics[topic]

        def __iter__(self):
            return iter(self._msgs)

    class FakeProducer:
        def __init__(self, **kw):
            pass

        def send(self, topic, payload):
            sent.append((topic, json.loads(payload)))

        def flush(self):
            pass

    mod = _types.ModuleType("kafka")
    mod.KafkaConsumer = FakeConsumer
    mod.KafkaProducer = FakeProducer
    monkeypatch.setitem(_sys.modules, "kafka", mod)

    import pathway_tpu as pw

    pw.reset()
    _sys.path.insert(0, str(__import__("os").path.join(REPO_ROOT, "templates")))
    try:
        import kafka_etl

        kafka_etl.build(
            {"bootstrap.servers": "broker:9092", "group.id": "g"},
            "timezone1", "timezone2", "unified",
        )
        pw.run(monitoring_level=None, commit_duration_ms=50)
    finally:
        _sys.path.pop(0)
        _sys.modules.pop("kafka_etl", None)

    out = [p for topic, p in sent if topic == "unified"]
    assert len(out) == 2, sent
    # both zones collapse to the SAME epoch instant (15:01:52.884 UTC)
    stamps = {p["timestamp"] for p in out}
    assert len(stamps) == 1, stamps
    assert next(iter(stamps)) == pytest.approx(1707145312884.548), stamps
    assert {p["message"] for p in out} == {"NYC event", "Paris event"}
