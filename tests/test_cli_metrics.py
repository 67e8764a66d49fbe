"""CLI launcher + metrics endpoint tests (reference: tests/cli +
http_server.rs behavior)."""

import json
import sys
import urllib.request

import pathway_tpu as pw
from pathway_tpu.cli import main as cli_main

from .utils import T


def test_spawn_launches_n_processes(tmp_path):
    script = tmp_path / "prog.py"
    script.write_text(
        "import os, pathlib\n"
        "pid = os.environ['PATHWAY_PROCESS_ID']\n"
        "n = os.environ['PATHWAY_PROCESSES']\n"
        "coord = os.environ['PATHWAY_COORDINATOR_ADDRESS']\n"
        f"pathlib.Path(r'{tmp_path}', 'out-' + pid).write_text(n + ' ' + coord)\n"
    )
    rc = cli_main(
        ["spawn", "-n", "3", "--first-port", "19876", sys.executable, str(script)]
    )
    assert rc == 0
    for pid in range(3):
        content = (tmp_path / f"out-{pid}").read_text()
        assert content == "3 127.0.0.1:19876"


def test_spawn_propagates_failure(tmp_path):
    script = tmp_path / "bad.py"
    script.write_text("import sys; sys.exit(3)\n")
    rc = cli_main(["spawn", "-n", "2", sys.executable, str(script)])
    assert rc == 3


def test_spawn_refuses_several_processes_on_an_accelerator_host(
    tmp_path, monkeypatch, capsys
):
    """A chip belongs to one process: with an accelerator present, N > 1
    is refused with a message before anything starts; N == 1 is not
    probed at all."""
    from pathway_tpu import cli

    script = tmp_path / "prog.py"
    script.write_text(
        f"import pathlib; pathlib.Path(r'{tmp_path}', 'ran').write_text('x')\n"
    )
    monkeypatch.setattr(cli, "_accelerator_backend", lambda env: "tpu")
    rc = cli_main(["spawn", "-n", "2", sys.executable, str(script)])
    assert rc == 2
    assert not (tmp_path / "ran").exists()
    assert "one process" in capsys.readouterr().err
    assert cli_main(["spawn", "-n", "1", sys.executable, str(script)]) == 0
    assert (tmp_path / "ran").exists()
    # the probe itself: a CPU-pinned environment needs no subprocess
    monkeypatch.undo()
    assert cli._accelerator_backend({"JAX_PLATFORMS": "cpu"}) is None


def test_replay_sets_persistence_env(tmp_path):
    script = tmp_path / "prog.py"
    script.write_text(
        "import os, pathlib\n"
        f"pathlib.Path(r'{tmp_path}', 'env').write_text(\n"
        "    os.environ.get('PATHWAY_PERSISTENCE_MODE','') + ' ' +\n"
        "    os.environ.get('PATHWAY_PERSISTENT_STORAGE',''))\n"
    )
    rc = cli_main(
        [
            "replay",
            "--record-path",
            str(tmp_path / "rec"),
            "--mode",
            "speedrun",
            sys.executable,
            str(script),
        ]
    )
    assert rc == 0
    mode, path = (tmp_path / "env").read_text().split(" ", 1)
    assert mode == "SPEEDRUN"
    assert path == str(tmp_path / "rec")


def test_metrics_endpoint_scrapes():
    from pathway_tpu.internals.metrics import start_metrics_server

    t = T("""
      | a
    1 | 1
    2 | 2
    """)
    out = t.select(b=pw.this.a * 2)
    pw.run(monitoring_level=None)
    server = start_metrics_server(pw.G.engine_graph, port=0)
    try:
        base = f"http://127.0.0.1:{server.port}"
        body = urllib.request.urlopen(f"{base}/metrics", timeout=5).read().decode()
        assert "pathway_operator_rows_in_total" in body
        assert "pathway_resident_rows" in body
        status = json.loads(
            urllib.request.urlopen(f"{base}/status", timeout=5).read()
        )
        assert status["operators"] >= 2
        assert status["resident_rows"] >= 4
    finally:
        server.stop()
