"""Host milliseconds per job in the program's ``offload.verify`` span (the
static verify gate of ``Session.submit``), traced window."""

from bench.spans import per_job_ms


def read(rec):
    return per_job_ms(rec, "offload.verify")
