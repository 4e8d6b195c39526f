"""Process start to the first timed request: imports, model and frames,
compiles (or cache loads) and warm-up."""


def read(record):
    return record["setup_s"]
