"""Process start to the window's start: weights, engine, the cell's
rung compiles and the traffic warm-up (host clock)."""


def read(rec):
    return rec["setup_s"]
