"""Peak device memory of the run, ``memory_stats()["peak_bytes_in_use"]``
on the fullest chip, in GiB."""


def read(run):
    return run.peak_bytes / 2**30
