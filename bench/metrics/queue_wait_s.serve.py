"""Serving engine admission: mean seconds from a request's due time to the
start of the round that admitted it, over the admitted requests."""


def read(run):
    return run.driver.queue_wait_s()
