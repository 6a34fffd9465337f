"""Host milliseconds per job in the program's ``offload.fetch`` span (the
blocking ``device_get`` of ``JobHandle.wait``: the device run still
pending and the device-to-host transfer), traced window."""

from bench.spans import per_job_ms


def read(rec):
    return per_job_ms(rec, "offload.fetch")
