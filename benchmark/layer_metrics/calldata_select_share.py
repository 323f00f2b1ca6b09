"""Of the calldata reads at a symbolic offset (solc reads the length of
an ``address[]`` or a ``bytes`` argument so), the share that a select
over the transaction's bytes answered rather than a havoc leaf tied to
nothing: 100 x select / (select + havoc) of
``engine_calldata_symreads_total{how}`` (analysis/symbolic.py sums, at
each harvest, the reads on the paths that ended the transaction without
error or revert), differenced over the window. A program without the
counter, or a window without such a read, gives nothing to read. Layer:
engine. Moves ``contracts_per_min``."""

HOW = 'engine_calldata_symreads_total{how="%s"}'


def read(obs: dict):
    if obs.get("kind") != "campaign":
        return None
    after = (obs.get("registry_after") or {}).get("counters", {})
    before = (obs.get("registry_before") or {}).get("counters", {})
    if HOW % "select" not in after:
        return None
    select, havoc = (after.get(HOW % how, 0.0) - before.get(HOW % how, 0.0)
                     for how in ("select", "havoc"))
    if select + havoc <= 0:
        return None
    return 100.0 * select / (select + havoc)
