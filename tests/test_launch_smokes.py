"""The two launch smokes CI runs as jobs, run here at a small size so the
default suite covers them too: the concurrent server against
``Plan.execute()`` oracles, and the out-of-core storage round trip."""
import argparse

from repro.launch import serve, storage
from repro.storage import sort as external_sort

#: rows per relation: more than the forced 64-row chunk (``rows // 64``
#: stays below 64 here), so each relation sorts as several runs
STORAGE_ROWS = 1000


def test_serve_smoke_passes():
    args = argparse.Namespace(
        host="127.0.0.1", workers=4, fusion_window=0.002, scale=1000,
        engine="jax", clients=4, queries_per_client=3, view_batches=3,
    )
    assert serve.run_smoke(args) == 0


def test_storage_smoke_passes(monkeypatch):
    """Every relation is larger than the smoke's forced chunk (64 rows),
    so its encodes go through multi-run external sorts and the merge."""
    runs = []
    real = external_sort.sort_chunks_to_runs

    def counted(directory, chunks):
        out = real(directory, chunks)
        runs.append(len(out))
        return out

    monkeypatch.setattr(external_sort, "sort_chunks_to_runs", counted)
    assert storage.smoke(STORAGE_ROWS, None) == 0
    assert max(runs) > 1

