"""The whole per-signature arm after an RLC reject (crypto/batch
._device_verify: every h hashed and the batch packed in Python, the
per-signature program's enqueue, the wait for its verdicts): span
verify.localize.
0 where the window saw no reject, None where it saw some and the program
opened no such span (benchmark/reject_metrics.py)."""

from benchmark import reject_metrics


def read(run):
    return reject_metrics.span_ms_per_unit(run, "verify.localize")
