"""The device's `peak_bytes_in_use` over the process, read after the
window and before the check, in GiB."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 2 ** 30
