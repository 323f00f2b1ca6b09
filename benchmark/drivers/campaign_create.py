"""Driver ``campaign_create``: the closed loop of driver ``campaign``,
unchanged, over contracts that come with their creation code, as a
directory of ``X.bin`` + ``X.bin-runtime`` pairs gives them to
``analyze --corpus``. ``campaign`` builds its campaigns from ``(name,
code)`` pairs; here each pair is handed on as the record ``(name, code,
creation code)``, so ``CorpusCampaign`` runs every constructor and starts
the message calls from the storage it left. Set-up, window, rate,
spans, profiler slice and the comparison that decides ``correct`` are
that driver's.

A program that cannot deploy in a campaign (the parent of the PR that
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
    by its name."""

    def __init__(self, corpus):
        self.corpus, self.creation = corpus, {}
        self.BATCH = corpus.BATCH

    def batch(self, *args, **kw) -> list:
        out = self.corpus.batch(*args, **kw)
        self.creation.update((c["name"], c["creation"]) for c in out)
        return out


def records(pairs, creation: dict) -> list:
    """Where the creation code is handed over."""
    return [(name, code, creation[name]) for name, code in pairs]


def run(ctx) -> dict:
    from mythril_tpu.mythril.campaign import CorpusCampaign

    if "creations" not in inspect.signature(
            CorpusCampaign._explore_batch).parameters:
        print("benchmark/drivers/campaign_create.py: this program's "
              "CorpusCampaign takes no creation code: not measuring",
              file=sys.stderr)
        raise SystemExit(4)
    spec = importlib.util.spec_from_file_location(
        "driver_campaign_deploying", os.path.join(HERE, "campaign.py"))
    base = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(base)
    corpus = Recorded(ctx.corpus)
    build = base.build_campaign
    base.build_campaign = lambda args, pairs, **kw: build(
        args, records(pairs, corpus.creation), **kw)
    return base.run(SimpleNamespace(**{**vars(ctx), "corpus": corpus}))
