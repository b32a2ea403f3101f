"""Tokens the window's calls forwarded, over the window's whole length
(its first call's submission to its last call's result).  Source: the
host clock."""


def read(r):
    return r.rec.tokens() / r.rec.seconds if r.rec.seconds > 0 else None
