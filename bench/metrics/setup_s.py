"""Process start to the window's opening: device init and imports, the
weights, the traffic and the program, the warm-up.  Source: the host
clock."""


def read(r):
    return r.setup_s
