"""Device self time of the ops under the program's ``chunk`` named scope
(the mixed step's prompt-chunk pass), per control slot in the traced
segment: ``run.trace["scopes"]`` from ``xspace_scopes.scope_split``.
Absent where the trace has no scoped ops."""


def read(run):
    scopes = (run.trace or {}).get("scopes")
    n = len(run.traced_slots())
    if not scopes or not n or scopes["chunk"] + scopes["decode"] <= 0:
        return None
    return 1e3 * scopes["chunk"] / n
