"""Set-up: building the system, making its data and weights, warming every
shape the mix uses (compiles included)."""


def read(facts):
    return facts["setup_s"]
