"""The benchmark's CPU tests: eight host devices (the same setting as the
repository's own tests), and no persistent compilation cache (a run would
otherwise turn it on for the whole process)."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("NPROC", "16")

import pytest  # noqa: E402


@pytest.fixture
def no_cache(monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "enable", lambda: "off")


@pytest.fixture
def cpu_chips():
    import jax
    return lambda n: jax.devices()[:n]
