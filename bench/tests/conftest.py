import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Route the event-wheel ops to their Pallas kernels (interpret mode on
    the CPU), as they run on a TPU."""
    from repro.kernels.event_wheel import ops
    monkeypatch.setattr(ops, "auto_impls", lambda: {
        "compact_ids": "pallas", "compact_gather": "pallas",
        "spike_compact": "pallas", "segment_rank": "pallas"})
