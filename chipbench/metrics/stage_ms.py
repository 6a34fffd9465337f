"""Host milliseconds per job in the program's ``offload.stage`` spans
(``DispatchPlan.stage``, ``stage_renamed`` and ``stage_args``: the
host-to-device enqueue and host snapshot copies), traced window."""

from bench.spans import per_job_ms


def read(rec):
    return per_job_ms(rec, "offload.stage")
