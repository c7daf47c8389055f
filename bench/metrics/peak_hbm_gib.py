"""Peak device memory in use, GiB, read after the window.

``memory_stats()["peak_bytes_in_use"]`` of the fullest chip: the group
states users keep resident plus whatever copies a launch makes.
"""


def read(run):
    return run.peak_bytes / 2**30
