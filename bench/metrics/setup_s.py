"""Set-up time: process start to the first due request, compiles and
loads from the compile cache included (host clock)."""


def read(rec):
    return rec["setup_s"]
