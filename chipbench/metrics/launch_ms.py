"""Host milliseconds per job in the program's ``offload.launch`` span
(``OffloadRuntime._launch``: completion-unit programming and the
asynchronous call of the job's program), traced window."""

from bench.spans import per_job_ms


def read(rec):
    return per_job_ms(rec, "offload.launch")
