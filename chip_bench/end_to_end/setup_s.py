"""Process start to the first timed chunk: build, place, compile, warm-up."""


def read(rec):
    return rec.setup_s
