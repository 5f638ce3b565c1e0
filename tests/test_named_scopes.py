"""The program's names for the device trace and the host timeline.

The train step names its phases (``fwd_bwd``, ``attack``, ``defense``,
``optimizer``, ``telemetry``) and the safeguard and models their parts
with ``jax.named_scope``; the names reach the compiled step's ``op_name``
metadata, where a device trace's ops can be put down to them.
``Trainer.run`` wraps each step in the profiler spans
``repro.step``/``batch``/``dispatch``/``log``.  All of it runs on the
CPU: the compiled CPU step carries the same metadata as the TPU one (the
v5e compile is checked in ``test_tpu_compile.py``)."""

import functools
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs as C
from repro.launch import train as train_lib
from repro.train.trainer import Trainer

PHASES = ("fwd_bwd", "attack", "defense", "optimizer", "telemetry")

_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s.*?\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s")
_CALLED = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


@functools.lru_cache(maxsize=None)
def _compiled_text(arch: str, defense: str) -> str:
    args = train_lib.parse_args([
        "--workers", "4", "--byz", "1", "--batch", "4", "--seq", "32",
        "--attack", "sign_flip", "--defense", defense, "--t0", "2",
        "--t1", "4"])
    tr = train_lib.build_trainer(C.get_smoke(arch), args)
    batch = next(tr.data_iter)
    return tr.step_fn.lower(tr.state, batch).compile().as_text()


def _instructions(text: str) -> dict:
    """``{instruction: (opcode, op_name)}`` over every computation of an
    HLO module's text.  An instruction the compiler made without an
    ``op_name`` takes that of its first operand with one (a rewritten dot
    belongs to what it reads), else that of the instruction running its
    computation (a loop body's ops, the loop's); ``""`` where there is
    none."""
    own, args, comp_of, caller, comp = {}, {}, {}, {}, None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            name, rhs = m.group(1), line.split(" = ", 1)[1]
            op = _OP_NAME.search(line)
            own[name] = (m.group(2), op.group(1) if op else "")
            args[name] = _OPERAND.findall(rhs.split("metadata=")[0])
            comp_of[name] = comp
            for c in _CALLED.findall(line):
                caller.setdefault(c, name)
        elif line.endswith("{") and not line.startswith(" "):
            comp = _COMP.match(line).group(1)
    resolved = {}

    def op_name(name):
        if name not in resolved:
            resolved[name] = ""                  # a cycle resolves to ""
            up = [caller[comp_of[name]]] if comp_of[name] in caller else []
            resolved[name] = own[name][1] or next(
                (r for k in args[name] + up if k in own
                 for r in [op_name(k)] if r), "")
        return resolved[name]

    return {k: (code, op_name(k)) for k, (code, _) in own.items()}


def _op_names(text: str) -> list:
    return [n for _, n in _instructions(text).values()]


def _phase(op_name: str):
    """The first path component of ``op_name`` that is a phase."""
    return next((c for c in op_name.split("/") if c in PHASES), None)


def _components(op_name: str) -> list:
    """The path components of ``op_name``, each without the transforms
    printed around a scope at the top of a transformed function
    (``vmap(jvp(head))`` reads ``head``)."""
    return [re.sub(r"^(?:\w+\()+|\)+$", "", c) for c in op_name.split("/")]


def _under(op_name: str, outer: str, inner: str) -> bool:
    """``inner`` is a path component below ``outer``."""
    parts = _components(op_name)
    return outer in parts and inner in parts[parts.index(outer) + 1:]


@pytest.mark.parametrize("defense", ["mean", "krum", "centered_clip",
                                     "safeguard_double"])
def test_phase_scopes_in_compiled_step(defense):
    text = _compiled_text("mamba2-130m", defense)
    assert set(PHASES) <= {_phase(n) for n in _op_names(text)}


@pytest.mark.parametrize("arch", ["mamba2-130m", "tinyllama-1.1b",
                                  "recurrentgemma-2b", "granite-moe-3b-a800m"])
def test_every_loop_matmul_and_kernel_carries_a_phase(arch):
    instrs = _instructions(_compiled_text(arch, "safeguard_double"))
    heavy = {k: v for k, v in instrs.items()
             if v[0] in ("while", "dot", "convolution", "custom-call")}
    assert any(code == "while" for code, _ in heavy.values())
    assert any(code in ("dot", "convolution") for code, _ in heavy.values())
    # the step's one rng split, whose keys feed several phases, is the one
    # loop under none
    missing = [(k, n) for k, (_, n) in heavy.items() if _phase(n) is None
               and "/jit(_threefry_split)/" not in n]
    assert not missing


@pytest.mark.parametrize("arch,names", [
    ("mamba2-130m", {"embed", "mixer", "ssd", "head"}),
    ("tinyllama-1.1b", {"embed", "mixer", "mlp", "head"})])
def test_model_scopes_under_fwd_bwd(arch, names):
    op_names = _op_names(_compiled_text(arch, "safeguard_double"))
    assert all(any(_under(n, "fwd_bwd", k) for n in op_names)
               for k in names)
    # the backward pass keeps the names: transpose(...) ops under the mixer
    assert any("transpose(" in n and _under(n, "fwd_bwd", "mixer")
               for n in op_names)


def test_safeguard_sub_scopes_under_defense():
    op_names = _op_names(_compiled_text("mamba2-130m", "safeguard_double"))
    for sub in ("accumulate", "distance", "filter", "aggregate"):
        assert any(_under(n, "defense", sub) for n in op_names), sub


class _State:
    """A train state that counts the reads of its ``step``."""

    def __init__(self, reads):
        self.reads = reads
        self.params = None

    @property
    def step(self):
        self.reads.append(1)
        return 0


def _trainer(reads, log_every):
    step = lambda state, batch: (_State(reads), {"loss": jnp.float32(1.0)})
    return Trainer(_State(reads), step, iter(lambda: 0, None),
                   log_every=log_every)


def test_run_reads_state_step_only_at_log_boundaries():
    reads = []
    tr = _trainer(reads, log_every=5)
    tr.run(10, verbose=False)
    assert len(reads) == 2 and tr.dispatched == 10
    tr.run(3, verbose=False)                     # last step of a run logs
    assert len(reads) == 3 and tr.dispatched == 13


def test_run_writes_host_spans(tmp_path):
    from jax.profiler import ProfileData
    tr = _trainer([], log_every=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr.run(3, verbose=False)
        tr.run(2, verbose=False)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    events = [e for p in ProfileData.from_file(path).planes
              if p.name.startswith("/host:") for line in p.lines
              for e in line.events if e.name.startswith("repro.")]
    steps = sorted((e for e in events if e.name == "repro.step"),
                   key=lambda e: e.start_ns)
    assert [dict(e.stats)["step_num"] for e in steps] == [0, 1, 2, 3, 4]
    count = lambda n: sum(e.name == n for e in events)
    assert count("repro.batch") == count("repro.dispatch") == 5
    assert count("repro.log") == 3                # steps 2, 3 and 5
    # every inner span lies inside a step span
    for e in events:
        if e.name != "repro.step":
            assert any(s.start_ns <= e.start_ns and
                       e.start_ns + e.duration_ns
                       <= s.start_ns + s.duration_ns for s in steps)
