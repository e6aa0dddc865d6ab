"""The control of the exact objective check.

The configurations state F exactly: float32 sums of integer products on
the device, or float64 on the host.  The control puts the reference in the
program's place computed one precision lower, in bfloat16 products summed
in float32, the step that would tempt a later change to the kernels, and
reads the same gap the check reads.  It has to fail the check's limit.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

import reference


def objective_bf16(C: np.ndarray, M: np.ndarray, nodes: np.ndarray,
                   perm: np.ndarray) -> float:
    phys = nodes[perm]
    prod = (C.astype(ml_dtypes.bfloat16)
            * M[np.ix_(phys, phys)].astype(ml_dtypes.bfloat16))
    return float(prod.astype(np.float32).sum(dtype=np.float32))


def readings(window, M: np.ndarray) -> dict:
    """The check's F gap with the control's answers in place of the
    program's, over the same jobs."""
    answers = {}
    for c in window.commits:
        if c.in_window and c.job_id in window.answers:
            perm = np.asarray(window.answers[c.job_id][0])
            answers[c.job_id] = (perm, objective_bf16(
                window.flows(c.job_id), M, c.nodes, perm))
    commits = [c for c in window.commits if c.job_id in answers or not c.in_window]
    got = reference.check(commits, answers, window.flows, M)
    return {"f_gap": got.f_gap, "jobs": got.jobs}
