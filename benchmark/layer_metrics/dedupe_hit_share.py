"""Share of the window's answered requests that the daemon served from
its dedupe layers (``served_from`` starting with ``dedupe``: the verdict
store or an identical request in flight), counted at the client. Layer:
serve scheduler. Moves ``verdict_p50_s``."""


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    done = [r for r in obs["requests"] if r.get("status") == "ok"]
    if not done:
        return None
    hits = sum(1 for r in done
               if str(r.get("served_from") or "").startswith("dedupe"))
    return 100.0 * hits / len(done)
