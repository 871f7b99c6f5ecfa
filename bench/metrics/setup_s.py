"""Set-up seconds: from the start of the command to the first measured run
(starting JAX, writing the tables, the rehearsal, the cold pass, compiles)."""


def reduce(bundle):
    return bundle["setup_s"]
