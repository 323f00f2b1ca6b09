"""Helper the readers of labelled counters share: the registry's
counters differenced over the window, by their labels."""

import re

LABEL = re.compile(r'(\w+)="([^"]*)"')


def window(obs: dict, name: str) -> list:
    """``(labels, after - before)`` of every series of counter ``name``
    in a campaign run; a program without the counter gives none."""
    if obs.get("kind") != "campaign":
        return []
    after = (obs.get("registry_after") or {}).get("counters", {})
    before = (obs.get("registry_before") or {}).get("counters", {})
    return [(dict(LABEL.findall(key)), value - before.get(key, 0.0))
            for key, value in after.items() if key.startswith(name + "{")]


def fate_share(obs: dict, name: str, fate: str, message_calls: bool = False):
    """100 x the series of ``name`` with ``fate`` over all of its series
    in the window, ``tx`` 0 (the creation transaction) left out with
    ``message_calls``; None where the window counted nothing."""
    series = [(lb["fate"], n) for lb, n in window(obs, name)
              if not (message_calls and lb.get("tx") == "0")]
    total = sum(n for _, n in series)
    if total <= 0:
        return None
    return 100.0 * sum(n for f, n in series if f == fate) / total
