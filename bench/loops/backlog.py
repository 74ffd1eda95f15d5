"""Backlog: the next group of the mix is submitted as soon as ``run``
returns, each request due on submission; no group starts after the
window's ``seconds``, and at least two are served."""


def drive(w):
    k = 0
    while k < 2 or w.elapsed() < w.seconds:
        w.call(w.requests(k))
        k += 1
