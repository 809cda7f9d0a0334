"""The synthetic run of ``tests/test_bench.py`` (``_fake_run``) also
carries what the serving thread's readers read: two ``gateway.pump``
ticks, one with a ``continuous.sync.*`` child, and the
``forwards_by_rows`` counters. Keys are only added, so every reader in
``BENCHMARK.json`` that lists the cell is read by
``test_readers_read_their_cell_and_stay_in_range``."""
import pytest

SERVING_THREAD_SPANS = [("gateway.pump", 0.9, 1.3),
                        ("continuous.sync.0-4", 1.1, 1.25),
                        ("gateway.pump", 2.0, 2.1)]
ROW_COUNTERS = {'forwards_by_rows{rows="1"}': 4,
                'forwards_by_rows{rows="8"}': 12}


@pytest.fixture(autouse=True)
def _serving_thread_in_fake_runs(request, monkeypatch):
    fake_run = getattr(request.module, "_fake_run", None)
    if fake_run is None:
        return

    def with_serving_thread(cell, traced):
        run = fake_run(cell, traced)
        run["counters"].update(ROW_COUNTERS)
        if run["trace"] is not None:
            run["trace"]["spans"] = (list(run["trace"]["spans"])
                                     + SERVING_THREAD_SPANS)
        return run

    monkeypatch.setattr(request.module, "_fake_run", with_serving_thread)
