"""Extract server: 95th percentile over the frames that reached the
extract of their request's queue wait (the program's ``queue_wait`` span,
submit → the launch that carries it), ms (device trace)."""
import spans


def read(run):
    return spans.queue_wait_p95_ms(spans.of_run(run))
