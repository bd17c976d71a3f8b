"""Process start to the opening of the timed window: imports, device
start, data generation when the seed's data is not there yet, native
build, compilation and warm-up."""


def read(rec):
    return rec["setup_s"]
