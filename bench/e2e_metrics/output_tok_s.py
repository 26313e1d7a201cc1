"""Output tokens that reached the host inside the window, per second of
the window."""


def read(run):
    end = run.t0 + run.seconds
    n = 0
    for s in run.done():
        r = s.req
        n += sum(1 for k in range(len(r.generated))
                 if run.landing(run.token_slot(r, k)) <= end)
    return n / run.seconds
