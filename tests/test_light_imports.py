"""Light CLI paths must not initialize a JAX backend (round-5 invariant).

``campaign-merge`` / ``function-to-hash`` / ``version`` are pure host
work; a module-level jnp array anywhere in their import chains commits
to a device at import time, which on a wedged TPU runtime hangs the
process before ``main()`` runs (the round-5 ``u256._MASK32`` bug). Locked in by asserting, in a clean
subprocess, that the chains import with ``xla_bridge._backends`` still
empty.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import sys
sys.path.insert(0, {repo!r})
{body}
from jax._src import xla_bridge
assert not xla_bridge._backends, (
    "backend initialized by a light import: %r" % (xla_bridge._backends,))
print("CLEAN")
"""


def _assert_clean(body: str):
    # a clean env (no JAX_PLATFORMS pin): the invariant is that the
    # import itself never ASKS for a backend, whatever the platform
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    r = subprocess.run(
        [sys.executable, "-c", _PROBE.format(repo=REPO, body=body)],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert r.returncode == 0 and "CLEAN" in r.stdout, (
        f"light import touched a backend:\n{r.stdout}\n{r.stderr[-2000:]}")


def test_campaign_merge_chain_is_backend_free():
    _assert_clean(
        "from mythril_tpu.mythril.campaign import merge_campaigns\n"
        "assert merge_campaigns([{'contracts': 1}])['contracts'] == 1")


def test_signature_keccak_chain_is_backend_free():
    _assert_clean(
        "from mythril_tpu.utils.signatures import selector_of\n"
        "assert selector_of('transfer(address,uint256)') == 'a9059cbb'")


def test_cli_parser_and_version_are_backend_free():
    _assert_clean(
        "from mythril_tpu.interfaces.cli import create_parser\n"
        "create_parser().parse_args(['version'])")


# --- supervisors stay off the accelerator --------------------------------
# A process that supervises an engine worker must be able to describe
# the engine (CorpusCampaign + SymSpec) without initializing a backend:
# on a machine whose chip belongs to one process, a parent that took it
# leaves its worker none.

def test_isolated_campaign_is_backend_free():
    _assert_clean(
        "from mythril_tpu.symbolic import SymSpec\n"
        "from mythril_tpu.mythril.campaign import CorpusCampaign\n"
        "c = CorpusCampaign([], spec=SymSpec(storage=False),\n"
        "                   worker_isolation='on')\n"
        "assert c.worker_isolation and c._worker_config()['spec'] == "
        "SymSpec(storage=False)\n"
        "assert CorpusCampaign([], worker_isolation='on').spec == SymSpec()")


def test_serve_campaign_factory_is_backend_free():
    _assert_clean(
        "from mythril_tpu.serve.scheduler import default_campaign_factory\n"
        "assert default_campaign_factory({}).worker_isolation\n"
        "c = default_campaign_factory({'concrete_storage': True})\n"
        "assert c.spec.storage is False")


# The same, through the entry points and for the supervisor's whole
# life: the campaign / fleet worker / daemon runs its batches through a
# (protocol-only) engine worker and reports, and still holds no backend.
_STUB_WORKER = """
import contextlib, io, os, tempfile
import mythril_tpu.resilience as r

class StubSupervisor(r.WorkerSupervisor):
    def __init__(self, *a, **kw):
        kw["stub"] = True
        super().__init__(*a, **kw)

r.WorkerSupervisor = StubSupervisor
corpus = tempfile.mkdtemp()
for i, code in enumerate(("6000ff", "600160005500")):
    with open(os.path.join(corpus, "c%d.hex" % i), "w") as fh:
        fh.write(code)
FLAGS = ["--batch-size", "2", "--lanes-per-contract", "8",
         "--max-steps", "16", "--limits-profile", "test", "-o", "json"]

def cli(*argv):
    from mythril_tpu.interfaces.cli import main
    import sys
    sys.argv = ["myth", *argv]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        try:
            rc = main()
        except SystemExit as e:
            rc = e.code
    assert not rc, rc
    return out.getvalue()
"""


def test_supervised_cli_campaign_is_backend_free_for_its_whole_life():
    _assert_clean(
        _STUB_WORKER
        + "import json\n"
        "doc = json.loads(cli('analyze', '--corpus', corpus, *FLAGS,\n"
        "                     '--worker-isolation', 'on'))\n"
        "assert doc['batch_status'] == ['ok'], doc\n"
        "kinds = [e['kind'] for e in doc['backend_events']]\n"
        "assert 'worker_spawn' in kinds, kinds\n"
        "cli('analyze', '--corpus', corpus, *FLAGS, '--fleet',\n"
        "    tempfile.mkdtemp())\n")


def test_serve_daemon_is_backend_free_for_its_whole_life():
    _assert_clean(
        _STUB_WORKER
        + "import sys, time\n"
        "sys.path.insert(0, os.path.join(%r, 'tools'))\n"
        "import serve_client\n"
        "from mythril_tpu.serve import AnalysisDaemon, ServeOptions\n"
        "dm = AnalysisDaemon(ServeOptions(batch_size=2,\n"
        "    lanes_per_contract=8, max_steps=16,\n"
        "    limits_profile='test'), data_dir=tempfile.mkdtemp(), port=0)\n"
        "dm.start()\n"
        "try:\n"
        "    url = 'http://127.0.0.1:%%d' %% dm.port\n"
        "    sid = serve_client.submit(url, [('a', bytes.fromhex('6000ff')),"
        " ('b', bytes.fromhex('600160005500'))])['id']\n"
        "    res = serve_client.get_result(url, sid, wait=60.0)\n"
        "    assert res['state'] == 'done', res\n"
        "    dm.health()\n"
        "    time.sleep(1.0)  # the background prewarm pass gets a turn\n"
        "finally:\n"
        "    dm.shutdown('test')\n" % REPO)
