"""Seconds from the start of the process to the start of the window:
drawing the data, uploading R, fitting the filter, compiling and warming
up every shape of the cell's traffic."""


def read(run):
    return run.setup_s
