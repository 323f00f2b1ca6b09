"""Reference oracle for linked contracts: a plain multi-account EVM.

``tests/pyevm_ref.py``'s ``RefEVM`` runs one account and answers every
CALL with ``success = 1``. This is the same boring Python (ints and
dicts) over a WORLD of accounts: CALL / CALLCODE / DELEGATECALL /
STATICCALL run the callee's code as a frame with its own memory, over
the right account's storage, move the value, hand back the return data
and roll storage and balances back when the frame reverts or fails.
It shares no code with ``mythril_tpu``'s frames (``symbolic/engine.py``
``_h_sym_call`` / ``pop_frames``): the engine's linked systems are
diffed against it (``tests/test_linked_system.py``) and the corpus
``linked-v1``'s witnesses replayed in it.
"""

from __future__ import annotations

import copy
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

from pyevm_ref import M256, RefEnv, RefEVM, oc

ADDR = (1 << 160) - 1
CALLS = ("CALL", "CALLCODE", "DELEGATECALL", "STATICCALL")
WRITES = ("SSTORE", "LOG0", "LOG1", "LOG2", "LOG3", "LOG4", "SELFDESTRUCT",
          "CREATE", "CREATE2")


@dataclass
class Account:
    code: bytes = b""
    storage: Dict[int, int] = field(default_factory=dict)
    balance: int = 0


class Frame(RefEVM):
    """One message call's execution inside a :class:`World`."""

    def __init__(self, world, code, calldata, env, storage_of: int,
                 static: bool, depth: int):
        super().__init__(code, calldata, env=env)
        self.world, self.static, self.depth = world, static, depth
        self.storage_of = storage_of
        self.storage = world.accounts[storage_of].storage   # shared

    def step(self):
        op = self.code[self.pc] if self.pc < len(self.code) else 0x00
        info = oc.OPCODES.get(op)
        name = info.name if info else None
        self.world.steps += 1
        self.world.deepest = max(self.world.deepest, self.depth)
        if name is None or len(self.stack) < info.stack_in:
            return self._fail()
        if self.static and name in WRITES:
            return self._fail()
        st = self.stack
        if name in CALLS:
            self.pc += 1
            return self._call(name)
        if name in ("EXTCODESIZE", "BALANCE", "SELFBALANCE"):
            self.pc += 1
            if name == "SELFBALANCE":
                st.append(self.world.balance(self.env.address))
            else:
                a = st.pop() & ADDR
                st.append(len(self.world.code(a)) if name == "EXTCODESIZE"
                          else self.world.balance(a))
            return None
        return super().step()

    def _call(self, name):
        st = self.stack
        st.pop()                                    # gas
        to = st.pop() & ADDR
        value = st.pop() if name in ("CALL", "CALLCODE") else 0
        a_off, a_len, r_off, r_len = (st.pop() for _ in range(4))
        if self.static and name == "CALL" and value:
            return self._fail()
        data = self._mread(a_off, a_len)
        me = self.env.address
        if name == "DELEGATECALL":
            ok, ret = self.world.message(
                self.env.caller, me, self.env.callvalue, data,
                self.env.origin, self.static, self.depth + 1, code_of=to,
                transfer=False)
        elif name == "CALLCODE":
            ok, ret = self.world.message(
                me, me, value, data, self.env.origin, self.static,
                self.depth + 1, code_of=to)
        else:
            ok, ret = self.world.message(
                me, to, value, data, self.env.origin,
                self.static or name == "STATICCALL", self.depth + 1)
        self.returndata = ret
        self._mwrite(r_off, ret[:r_len])
        st.append(1 if ok else 0)


class World:
    """Accounts by address; the engine's seeding (every contract 10**18
    wei, the two EOAs 10**20) unless told otherwise."""

    def __init__(self, eoas=(), eoa_balance: int = 10 ** 20):
        self.accounts: Dict[int, Account] = {}
        for a in eoas:
            self.accounts[a] = Account(balance=eoa_balance)
        self.steps = 0
        self.deepest = 0
        self.max_steps = 200_000
        #: every value that moved: (from, to, wei)
        self.sent: List[Tuple[int, int, int]] = []
        self.destroyed: List[int] = []

    def code(self, a: int) -> bytes:
        return self.accounts[a].code if a in self.accounts else b""

    def balance(self, a: int) -> int:
        return self.accounts[a].balance if a in self.accounts else 0

    def _snapshot(self):
        return ({a: (dict(ac.storage), ac.balance)
                 for a, ac in self.accounts.items()}, len(self.sent))

    def _restore(self, snap):
        state, n_sent = snap
        for a in list(self.accounts):
            if a not in state:
                del self.accounts[a]
                continue
            storage, balance = state[a]
            self.accounts[a].storage.clear()
            self.accounts[a].storage.update(storage)
            self.accounts[a].balance = balance
        del self.sent[n_sent:]

    def message(self, sender: int, to: int, value: int, data: bytes,
                origin: int, static: bool = False, depth: int = 0,
                code_of: int = None, transfer: bool = True):
        """(success, return data) of one message call."""
        if depth > 1024:
            return False, b""
        snap = self._snapshot()
        if transfer and value:
            if self.balance(sender) < value:
                return False, b""
            self.accounts.setdefault(to, Account())
            self.accounts[sender].balance -= value
            self.accounts[to].balance += value
            self.sent.append((sender, to, value))
        code = self.code(to if code_of is None else code_of)
        if not code:
            return True, b""
        self.accounts.setdefault(to, Account())
        env = RefEnv(address=to, caller=sender, origin=origin,
                     callvalue=value)
        fr = Frame(self, code, data, env, to, static, depth)
        while not (fr.halted or fr.error):
            if self.steps >= self.max_steps:
                fr.error = True
                break
            fr.step()
        if fr.selfdestructed and not fr.error:
            self.destroyed.append(to)
        if fr.error or fr.reverted:
            self._restore(snap)
            return False, (fr.retval if fr.reverted and not fr.error
                           else b"")
        return True, fr.retval

    def deploy(self, address: int, creation: bytes, creator: int,
               balance: int = 10 ** 18) -> bool:
        """Run ``creation`` as the constructor of a new account at
        ``address``; the code it returns becomes the account's."""
        self.accounts[address] = Account(balance=balance)
        env = RefEnv(address=address, caller=creator, origin=creator)
        fr = Frame(self, creation, b"", env, address, False, 0)
        while not (fr.halted or fr.error):
            if self.steps >= self.max_steps:
                fr.error = True
                break
            fr.step()
        if fr.error or fr.reverted:
            del self.accounts[address]
            return False
        self.accounts[address].code = bytes(fr.retval)
        return True

    def storage(self) -> Dict[int, Dict[int, int]]:
        return {a: {k: v for k, v in ac.storage.items()}
                for a, ac in self.accounts.items() if ac.code}

    def clone(self) -> "World":
        return copy.deepcopy(self)
