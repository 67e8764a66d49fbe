import os

# Virtual 8-device CPU mesh for sharding tests.  Tests force the CPU whatever
# the environment says (the chip is checked by chip_smoke.py, not by pytest):
# the config is set BEFORE the first backend initialisation.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: spawns subprocess clusters / long-running"
    )


@pytest.fixture(autouse=True)
def fresh_graph():
    import pathway_tpu as pw

    pw.reset()
    yield
    pw.reset()


@pytest.fixture(scope="session")
def needs_native():
    """For a test of the native library itself.  It skips only where
    ``native.disable`` is set; anywhere else a library that did not build
    fails the test, with what the compiler said."""
    import logging

    from pathway_tpu import config, native

    if config.get("native.disable"):
        pytest.skip("native.disable is set")
    if not native.available():
        said = []
        handler = logging.Handler()
        handler.emit = lambda record: said.append(record.getMessage())
        logging.getLogger(native.__name__).addHandler(handler)
        try:
            native.build()
        finally:
            logging.getLogger(native.__name__).removeHandler(handler)
        pytest.fail("the native library is missing:\n" + "\n".join(said))
