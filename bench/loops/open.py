"""Open loop: requests arrive on the mix's schedule at ``rate_rps``
(``bench/loadgen.arrivals``) for the window's ``seconds``.  The client
is single-threaded: it submits whatever is due, calls ``run``, and
repeats; requests that come due during a ``run`` wait in its queue, and
the wait counts from their due time.  When nothing is due it sleeps
until the next arrival.  The window ends when every request has been
served."""
from bench import loadgen


def drive(w):
    n = max(2, loadgen.open_groups(w.traffic, w.group, w.seconds))
    due = loadgen.arrivals(w.traffic, n * w.group)
    reqs = [r for k in range(n) for r in w.requests(k, due)]
    i = 0
    while i < len(reqs):
        w.wait_until(reqs[i].due_s)
        now = w.elapsed()
        j = i + 1
        while j < len(reqs) and reqs[j].due_s <= now:
            j += 1
        w.call(reqs[i:j], [r.due_s for r in reqs[i:j]])
        i = j
