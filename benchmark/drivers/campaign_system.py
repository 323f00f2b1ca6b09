"""Driver ``campaign_system``: the closed loop of drivers ``campaign``
and ``campaign_create``, unchanged, over a corpus of LINKED SYSTEMS, as
a directory of ``X.bin`` + ``X.bin-runtime`` pairs with a manifest a
system (``S.system.json``) gives them to ``analyze --corpus``.
``campaign`` builds its campaigns from ``(name, code)`` pairs; here each
pair is handed on as the record ``(name, code, creation code, {"system",
"address"})``, which is what ``load_corpus_dir`` makes of a manifest's
member: ``CorpusCampaign`` keeps a system in one batch, gives its lanes
the members' accounts at their addresses, runs every constructor and
starts every member's message calls from the one world they left.
Set-up, window, rate, spans, profiler slice and the comparison that
decides ``correct`` are driver ``campaign``'s.

A program whose campaign takes no systems (the parent of the PR that
taught it) exits non-zero at once, before anything compiles.
"""

from __future__ import annotations

import importlib.util
import inspect
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))


class Recorded:
    """The cell's corpus, remembering every contract's creation code
    and its place in its system by its name."""

    def __init__(self, corpus):
        self.corpus, self.seen = corpus, {}
        self.BATCH = corpus.BATCH

    def batch(self, *args, **kw) -> list:
        out = self.corpus.batch(*args, **kw)
        self.seen.update((c["name"], c) for c in out)
        return out


def records(pairs, seen: dict) -> list:
    """Where the creation code and the manifest's entry are handed
    over."""
    return [(name, code, seen[name]["creation"],
             {"system": seen[name]["system"],
              "address": seen[name]["address"]})
            for name, code in pairs]


def run(ctx) -> dict:
    from mythril_tpu.mythril.campaign import CorpusCampaign

    if "links" not in inspect.signature(
            CorpusCampaign._explore_batch).parameters:
        print("benchmark/drivers/campaign_system.py: this program's "
              "CorpusCampaign takes no linked systems: not measuring",
              file=sys.stderr)
        raise SystemExit(4)
    spec = importlib.util.spec_from_file_location(
        "driver_campaign_linked", os.path.join(HERE, "campaign.py"))
    base = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(base)
    corpus = Recorded(ctx.corpus)
    build = base.build_campaign
    base.build_campaign = lambda args, pairs, **kw: build(
        args, records(pairs, corpus.seen), **kw)
    return base.run(SimpleNamespace(**{**vars(ctx), "corpus": corpus}))
