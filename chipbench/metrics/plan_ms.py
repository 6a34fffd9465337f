"""Host milliseconds per job in the program's ``offload.plan`` span
(``Session.submit`` from the verify gate to the launch: selection, cached
estimate, lint, SLO gate, runtime and stream lookup), traced window."""

from bench.spans import per_job_ms


def read(rec):
    return per_job_ms(rec, "offload.plan")
