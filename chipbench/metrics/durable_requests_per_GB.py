"""durable_requests_per_GB: GET + PUT requests on the durable (S3) tier over
the window, per input GB, from the tier's MetricsMiddleware counters."""


def read(run):
    return (run.durable_gets + run.durable_puts) / (run.input_bytes / 1e9)
