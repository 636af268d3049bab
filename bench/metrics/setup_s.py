"""Set-up: process start to the first timed solve, compilation included."""


def read(facts):
    return facts["setup_s"]
