"""Build seconds: device states, compiled steps and one launch per group."""


def read(run):
    return run.build_s
