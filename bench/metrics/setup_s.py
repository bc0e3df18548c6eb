"""setup_s: seconds from the start of the process to the first timed
step (weights drawn, programs loaded or compiled, traffic warmed up)."""


def read(rec):
    return rec.setup_s
