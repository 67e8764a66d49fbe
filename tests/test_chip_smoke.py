"""chip_smoke.py must not rot between chip runs: the same leg functions the
chip run calls, at toy width on the CPU with the Pallas kernel in interpret
mode; and the script itself refusing any platform that is not a TPU."""

from __future__ import annotations

import os
import subprocess
import sys

import jax.numpy as jnp

import chip_smoke
from pathway_tpu.observe import slo

from .utils import REPO_ROOT

_TINY = dict(n_layers=1, n_heads=2, vocab_size=4096, dtype=jnp.float32)
TOY = chip_smoke.Sizes(
    n_docs=1024, encode_chunk=256, n_queries=16, k=5, candidates=8,
    live_docs=64, live_commit=32, absorb_threshold=32,
    serve_threads=4, serve_requests=16, max_new_tokens=8, shard_docs=512,
    encoder=dict(dimension=32, max_length=64, **_TINY),
    cross=dict(dimension=32, max_length=128, **_TINY),
    generator=dict(dimension=32, max_length=64, **_TINY),
)


def test_smoke_legs_run_at_toy_width_on_cpu():
    """Every check of the chip run holds at toy sizes: kernel vs reference,
    self-retrieval, IVF recall, clean serves under live ingest with a
    donated absorb, slot-engine tokens == generate(), and the four-shard
    answer == the one-shard answer on four (virtual) devices."""
    try:
        encoder, exact, queries = chip_smoke.run_single_chip(
            TOY, interpret=True, require_pallas=False
        )
        chip_smoke.four_chip_leg(
            TOY, encoder, exact, queries, require_pallas=False
        )
    finally:
        # first-call compiles read as slow serves; do not leave the
        # latency objective burning for the tests that follow
        slo.reset()


def _run_script(cache_dir_env):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir_env
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO_ROOT, env=env,
        capture_output=True, text=True, timeout=120,
    )


def test_script_refuses_the_cpu_and_names_the_compile_cache(tmp_path):
    """``python chip_smoke.py`` off the chip: non-zero, a clear message, no
    result line.  Its first line prints ``jax.config.jax_compilation_cache_dir``
    as ``import pathway_tpu`` left it: JAX_COMPILATION_CACHE_DIR untouched
    when set, ``<checkout>/.jax_cache`` when not."""
    for env_value, want in (
        (None, os.path.join(REPO_ROOT, ".jax_cache")),
        (str(tmp_path / "x"), str(tmp_path / "x")),
    ):
        proc = _run_script(env_value)
        assert proc.returncode != 0
        assert "not a TPU" in proc.stderr and "no CPU mode" in proc.stderr
        assert "platform=cpu" in proc.stdout  # it said what it found ...
        assert '"ok"' not in proc.stdout  # ... and printed no result
        assert f"compile cache: {want}\n" in proc.stdout


def test_one_line_of_code_places_the_compile_cache():
    setters = []
    sources = [os.path.join(REPO_ROOT, f) for f in ("bench.py", "chip_smoke.py")]
    for root, _dirs, files in os.walk(os.path.join(REPO_ROOT, "pathway_tpu")):
        sources += [os.path.join(root, f) for f in files if f.endswith(".py")]
    for path in sources:
        with open(path) as f:
            for no, line in enumerate(f, 1):
                if "jax_compilation_cache_dir" in line and "update(" in line:
                    setters.append(f"{os.path.relpath(path, REPO_ROOT)}:{no}")
    assert len(setters) == 1, setters
    assert setters[0].startswith("pathway_tpu/__init__.py:")
