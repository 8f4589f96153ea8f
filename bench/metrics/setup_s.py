"""Process start to the first timed job: JAX start-up, data generation on
the device, compilation or compile-cache loads, and the warm-up job."""


def read(rec):
    return rec["setup_s"]
