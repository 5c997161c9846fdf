"""The served programs of granite-moe-1b-a400m, compiled at its published
widths for one TPU v5e chip.

The TPU compiler runs here on a described topology, with no chip
attached: a program the chip's compiler refuses, or one that does not
fit the chip's memory, fails here.  Nothing runs, so these tests say
nothing about results or times.  The kernels are compiled, not
interpreted: the wrappers in ``repro.kernels.ops`` would otherwise see
the CPU backend and take their interpret branch.
"""

import re

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.serving import ServingEngine

ARCH = "granite-moe-1b-a400m"
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr("repro.kernels.ops._on_cpu", lambda: False)
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def compile_for(one_chip, entry, program):
    eng = ServingEngine(get_config(ARCH))
    fn, args = eng.entry_programs(entry)[program]
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        args)
    return fn.lower(*args).compile(), args


@pytest.mark.parametrize("entry,program", [
    ("generate", "prefill"), ("generate", "decode"), ("score", "score")])
def test_served_program_fits_one_v5e(one_chip, entry, program):
    compiled, args = compile_for(one_chip, entry, program)
    mem = compiled.memory_analysis()
    param_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(args[0]))
    assert param_bytes > 2 * 2**30  # published widths, not a reduced config
    assert mem.argument_size_in_bytes >= param_bytes
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, (program, used)


# ------------------------------------------ the routed-expert decode kernel
_COMP = re.compile(r"^(?:ENTRY )?%([\w.-]+) ")
_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "pred": 1}


def computations(hlo: str) -> dict:
    """{computation name: its instruction lines} of an HLO module's text."""
    out, cur = {}, None
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m:
            cur = out.setdefault(m.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line)
    return out


def loop_computations(comps: dict) -> set:
    """The computations a while loop's body runs, nested calls included."""
    refs = {name: set(re.findall(r"(?:calls|to_apply|body|condition|"
                                 r"branch_computations)=\{?%([\w.-]+)",
                                 "\n".join(lines)))
            for name, lines in comps.items()}
    todo = {b for lines in comps.values() for line in lines
            for b in re.findall(r" while\(.*body=%([\w.-]+)", line)}
    seen = set()
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            todo |= refs.get(name, set())
    return seen


_VIEWS = frozenset({"parameter", "get-tuple-element", "tuple", "bitcast",
                    "while", "call", "conditional", "custom-call"})
_INSTR = re.compile(r"^\s*(?:ROOT )?%[\w.-]+ = (\w+)\[([\d,]*)\]\S* "
                    r"([\w-]+)\(")


def new_buffers(comps: dict) -> list:
    """(bytes, opcode) of every instruction that writes an array of its
    own (a copy, a slice, a fusion, ...), not a view of one; the insides
    of a fusion write nothing of their own."""
    fused = {c for lines in comps.values() for line in lines
             for c in re.findall(r" fusion\(.*calls=%([\w.-]+)", line)}
    out = []
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            m = _INSTR.match(line)
            if m and m.group(3) not in _VIEWS:
                n = _BYTES.get(m.group(1), 4)
                for d in filter(None, m.group(2).split(",")):
                    n *= int(d)
                out.append((n, m.group(3)))
    return out


def test_decode_reads_routed_experts_in_the_layer_loop(one_chip,
                                                      monkeypatch):
    """At batch 1 the decode program runs the ``moe_routed_decode`` kernel
    inside the layer loop, under the ``moe`` scope, on the whole expert
    stacks: no array the size of one layer's experts (64 MiB ``wi``,
    32 MiB ``wo``) is written, and no more temporary bytes than the
    capacity path's."""
    from repro.models import layers as L
    cfg = get_config(ARCH)
    wo_layer = (cfg.moe.n_experts * cfg.moe.d_expert_ff * cfg.d_model
                * 2)  # bf16
    with L.moe_paths() as seen:
        routed, _ = compile_for(one_chip, "generate", "decode")
    assert seen == {"routed"}
    comps = computations(routed.as_text())
    calls = {name: line for name, lines in comps.items() for line in lines
             if 'custom_call_target="tpu_custom_call"' in line
             and "%moe_routed_decode" in line}
    assert calls, "no moe_routed_decode kernel in the decode program"
    assert set(calls) <= loop_computations(comps)
    for line in calls.values():
        op = re.search(r'op_name="([^"]*)"', line).group(1)
        assert "moe" in op.split("/"), op
    big = [(n, op) for n, op in new_buffers(comps) if n >= wo_layer]
    assert not big, big

    monkeypatch.setattr(L, "moe_takes_routed_path", lambda *a: False)
    capacity, _ = compile_for(one_chip, "generate", "decode")
    assert "moe_routed_decode" not in capacity.as_text()
    assert (routed.memory_analysis().temp_size_in_bytes
            <= capacity.memory_analysis().temp_size_in_bytes)
