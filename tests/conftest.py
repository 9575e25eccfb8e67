"""Test bootstrap: put ``src`` on sys.path and load a hypothesis profile.

The profile replays the same examples on every run (``derandomize``) and
keeps no example database; hypothesis' other caches go to the temporary
directory, so a run writes nothing into the checkout.

The CI matrix selects a kernel datapath per leg via REPRO_KERNEL_BACKEND
(off | int8); tests read it through the ``kernel_backend`` fixture below so
the no-kernel and int8 paths are both exercised on every push.
"""
import os
import pathlib
import sys
import tempfile

import pytest
from hypothesis import configuration, settings

SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

configuration.set_hypothesis_home_dir(
    pathlib.Path(tempfile.gettempdir()) / "hypothesis")
settings.register_profile("repro", database=None, derandomize=True)
settings.load_profile("repro")


@pytest.fixture(scope="session")
def kernel_backend() -> str:
    """The kernel datapath selected by the CI matrix leg (default "off").

    Tests that exercise the train/serve hot paths parameterize on this so
    the {1, 4}-device x {off, int8} matrix covers every combination.
    """
    backend = os.environ.get("REPRO_KERNEL_BACKEND", "off")
    assert backend in ("off", "emulate", "int8"), backend
    return backend


@pytest.fixture(scope="session")
def overlap() -> str:
    """The backward-scan overlap mode selected by the CI matrix leg
    (default "off"; the 4-device jobs add overlap="on" legs so the
    software-pipelined dW reduce runs against a real device group)."""
    mode = os.environ.get("REPRO_OVERLAP", "off")
    assert mode in ("off", "on"), mode
    return mode
