"""Device, in a cell judged on tokens per second (above the knee):
``serve.device_idle``, the idle share of the traced time in which a
request was queued or active, in %."""
from bench.common import reader


def read(ctx):
    return reader("serve.device_idle")(ctx)
