"""Compile-only checks for a described TPU v5e (no chip attached).

The TPU compiler ships with the installed jaxlib, so it can compile for a
chip that is described rather than present.  These tests ask it for the
main-path programs at the sizes ``chip_smoke.py`` runs — the MP scenario
scan (per-op and fused ``round_step``) and the CL-ADMM scan — and for every
Pallas impl still registered in ``kernels.dispatch``, at a width it
supports.  Nothing runs, so nothing here is a measurement: a pass means the
compiler accepts the program and its buffers fit one chip's 16 GiB.

The topology is described inside a module-scoped fixture, and every test
skips where it cannot be described: only one process at a time may load
the TPU library, so no module may describe it while being imported.
"""

import functools
import importlib.util
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.sparse import DeviceTables
from repro.kernels import dispatch
from repro.kernels import sharded as _sh
from repro.kernels import sparse_mix as _sm
from repro.simulate import engines, get_scenario, scheduler

ROOT = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

HBM_BYTES = 16 * 2 ** 30
# k_max that random_geometric_topology(n, k=8, seed=0) gives at the
# chip_smoke sizes (500_000 -> 18, 200_000 -> 17)
MP_KMAX, CL_KMAX = 18, 17


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abstract(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tables(sh, n, k):
    i32 = jnp.int32
    S = functools.partial(_abstract, sh)
    return DeviceTables(S((n, k), i32), S((n, k), i32), S((n,), i32),
                        S((n, k)), S((n, k)), S((n, k)), S((n,)))


def _keys(sh, n_rec, record_every):
    return _abstract(sh, (n_rec, record_every, 2), jnp.uint32)


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_BYTES, m
    return total


@pytest.fixture(scope="module")
def mp_scan(one_chip):
    """The MP scenario scan compiled at smoke size, once per backend."""
    compiled = {}

    def get(backend):
        if backend not in compiled:
            n, k, p, B = cs.MP_N, MP_KMAX, cs.P_DIM, cs.MP_BATCH
            re_ = cs.RECORD_EVERY
            n_rec = cs.MP_ROUNDS // re_
            S = functools.partial(_abstract, one_chip)
            cond = get_scenario("lossy-10").make_conditions(cs.MP_ROUNDS)
            compiled[backend] = engines._scenario_scan.lower(
                _tables(one_chip, n, k), S((n,), jnp.bool_), S((n,)),
                S((n, p)), S((n,)), _keys(one_chip, n_rec, re_),
                S((n_rec,), jnp.int32), conditions=cond, alpha=cs.ALPHA,
                batch=B, record_every=re_, n_rec=n_rec, tel=False,
                backend=backend).compile()
        return compiled[backend]
    return get


@pytest.mark.parametrize("backend", [
    None, dispatch.ReproBackend.using(round_step="xla")],
    ids=["per_op", "fused_xla"])
def test_mp_scenario_scan_compiles_at_smoke_size(mp_scan, backend):
    # the slot table alone is n * k * p * 4 bytes
    assert _fits_one_chip(mp_scan(backend)) \
        > cs.MP_N * MP_KMAX * cs.P_DIM * 4


_HLO_OP = re.compile(r"%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\("
                     r"(?:.*calls=%([\w.-]+))?")


def _loop_body_ops(text):
    """(name, opcode, element count, opcodes of the fused computation) of
    every array-valued instruction at the top level of a while loop's
    body in an optimized HLO module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.removeprefix("ENTRY ").split()[0].lstrip("%")
            cur = comps.setdefault(name, [])
        elif cur is not None and line.startswith("  "):
            cur.append(line.strip().removeprefix("ROOT "))
    bodies = {b for lines in comps.values() for line in lines
              for b in re.findall(r"body=%([\w.-]+)", line)}
    ops = []
    for body in sorted(bodies):
        for line in comps[body]:
            m = _HLO_OP.match(line)
            if m is None:
                continue
            name, _, dims, opcode, calls = m.groups()
            count = int(np.prod([int(d) for d in dims.split(",") if d]))
            inner = {i.group(4) for i in map(_HLO_OP.match,
                                             comps.get(calls, [])) if i}
            ops.append((name, opcode, count, inner))
    return ops


def test_per_op_mp_round_writes_its_slot_table_only_by_scatter(mp_scan):
    """On the chip an f32 array is tiled (8, 128) over its last two dims,
    and k_max = 18 is no multiple of 8, so an (n, k_max, p) slot table is
    no bitcast of the flat (n * k_max, p) layout its slot scatters write:
    carried 3-D, it was relaid out into that layout and back every round,
    a whole-table copy each way.  The per-op round carries it flat, so in
    the loop bodies no reshape, copy or transpose outputs a table-sized
    array, and the only ops that write one are the two slot scatters."""
    size = cs.MP_N * MP_KMAX * cs.P_DIM
    ops = _loop_body_ops(mp_scan(None).as_text())
    assert ops, "no while loop body found"
    writes = [(name, opcode, inner) for name, opcode, count, inner in ops
              if count == size and opcode not in (
                  "parameter", "get-tuple-element", "bitcast")]
    relayouts = [name for name, opcode, _ in writes
                 if opcode in ("reshape", "transpose")
                 or opcode.startswith("copy")]
    assert not relayouts, relayouts
    assert len(writes) == 2, writes
    assert all("scatter" in inner for _, _, inner in writes), writes


def test_cl_scenario_scan_compiles_at_smoke_size(one_chip):
    n, k, p, B = cs.CL_N, CL_KMAX, cs.P_DIM, cs.CL_BATCH
    re_ = cs.RECORD_EVERY
    n_rec = cs.CL_ROUNDS // re_
    S = functools.partial(_abstract, one_chip)
    cond = get_scenario("churn-5").make_conditions(cs.CL_ROUNDS)
    tabs = _tables(one_chip, n, k)
    stream = jax.eval_shape(
        functools.partial(scheduler._draw_stream, conditions=cond, batch=B,
                          rounds=cs.CL_ROUNDS),
        tabs, S((n,), jnp.bool_), S((n,)),
        _abstract(one_chip, (cs.CL_ROUNDS, 2), jnp.uint32))
    ev = jax.tree_util.tree_map(
        lambda a: S((n_rec, re_) + a.shape[1:], a.dtype),
        stream._replace(active_frac=None))
    state0 = (tabs.nbr_idx, S((n, p)))           # warm start built in-jit
    compiled = engines._cl_scenario_scan.lower(
        tabs.nbr_w, tabs.deg_count, S((n,)), S((n,)), S((n, p)), state0, ev,
        (S((n,)),), (), mu=cs.MU, rho=cs.RHO, backend=None, tel=True,
        primal=None).compile()
    # at least the five (n, k, p) f32 arrays of state, each tiled (8, 128)
    # on its last two dims (the round carries no whole-state snapshot)
    tiled = n * -(-k // 8) * 8 * -(-p // 128) * 128 * 4
    assert _fits_one_chip(compiled) > 5 * tiled


def test_cl_round_loop_copies_no_whole_state(one_chip):
    """At the one-chip CL cell's widths (k_max = 16, p = 100; n cut to
    20,000 to compile in seconds) no computation of the v5e program but its
    entry copies an (n, k_max, p) state array or a relaid view of one: the
    round carries partner rows, not publish snapshots held across the
    scatters, and the edge scatters write the (n·k, p) view in place (a
    (row, slot) scatter makes the compiler carry the state slot-major and
    relay it every round)."""
    n, k, p, B, re_ = 20_000, 16, 100, 2_000, 25
    S = functools.partial(_abstract, one_chip)
    cond = get_scenario("churn-5").make_conditions(2 * re_)
    tabs = _tables(one_chip, n, k)
    stream = jax.eval_shape(
        functools.partial(scheduler._draw_stream, conditions=cond, batch=B,
                          rounds=2 * re_),
        tabs, S((n,), jnp.bool_), S((n,)), S((2 * re_, 2), jnp.uint32))
    ev = jax.tree_util.tree_map(
        lambda a: S((2, re_) + a.shape[1:], a.dtype),
        stream._replace(active_frac=None))
    hlo = engines._cl_scenario_scan.lower(
        tabs.nbr_w, tabs.deg_count, S((n,)), S((n,)), S((n, p)),
        (tabs.nbr_idx, S((n, p))), ev, mu=cs.MU,
        rho=cs.RHO).compile().as_text()
    whole = {f"f32[{n},{k},{p}]", f"f32[{p},{k},{n}]", f"f32[{k},{n},{p}]",
             f"f32[{n * k},{p}]", f"f32[{p},{n * k}]"}
    copies = [line.strip()[:120]
              for comp in re.split(r"\n(?=\S)", hlo)
              if not comp.startswith("ENTRY") for line in comp.splitlines()
              if (m := re.search(r"= (f32\[[\d,]+\])\S* copy\(", line))
              and m.group(1) in whole]
    assert " while(" in hlo and not copies, copies


@pytest.fixture(scope="module")
def cl_partition():
    """The 4-way partition of the graph ``chip_smoke.py --chips 4`` runs
    sharded CL-ADMM on (host numpy, ~20 s)."""
    from repro.simulate import greedy_partition, random_geometric_topology
    from repro.simulate.partition import GraphPartition
    g = random_geometric_topology(cs.SHARD_CL_N, k=cs.DEGREE, seed=cs.SEED)
    return g, GraphPartition.build(g, greedy_partition(g, 4, seed=cs.SEED), 4)


@pytest.mark.parametrize("exchange", ["all_gather", "ring"])
def test_sharded_cl_scan_compiles_at_four_chip_size(topo, cl_partition,
                                                    exchange):
    """Sharded CL-ADMM at n = 200,000 on a 2x2 v5e mesh: each chip holds
    about a quarter of the state, which one chip could not hold whole."""
    from repro.launch.sim_mesh import AGENT_AXIS
    from repro.simulate.partition import _local_capacities, _sharded_cl_scan
    g, part = cl_partition
    mesh = Mesh(np.array(topo.devices), (AGENT_AXIS,),
                axis_types=(AxisType.Auto,))
    rows = NamedSharding(mesh, P(AGENT_AXIS))
    R = functools.partial(_abstract, rows)
    rep = NamedSharding(mesh, P())
    n, k, p, B = g.n, g.k_max, cs.P_DIM, cs.SHARD_CL_BATCH
    rounds, re_ = cs.SHARD_CL_ROUNDS, cs.RECORD_EVERY
    Pm = 4 * part.shard_size
    cond = get_scenario("churn-5").make_conditions(rounds)
    stream = jax.eval_shape(
        functools.partial(scheduler._draw_stream, conditions=cond, batch=B,
                          rounds=rounds),
        _tables(rep, n, k), _abstract(rep, (n,), jnp.bool_),
        _abstract(rep, (n,)), _abstract(rep, (rounds, 2), jnp.uint32))
    stream = jax.tree_util.tree_map(
        lambda a: _abstract(rep, a.shape, a.dtype), stream)
    halo = {name: R(getattr(part, name).shape, jnp.int32) for name in
            ("fetch", "bnd_pos", "halo_src_shard", "halo_src_pos")}
    E, U = _local_capacities(B, 4, None)
    compiled = _sharded_cl_scan.lower(
        mesh, stream, R((Pm, p)), R((Pm, k), jnp.int32), R((Pm, k)),
        R((Pm,), jnp.int32), R((Pm,)), R((Pm,)), R((Pm, p)), **halo,
        mu=cs.MU, rho=cs.RHO, k=k, m=part.shard_size, H=part.halo_size,
        E=E, U=U, n_rec=rounds // re_, record_every=re_,
        exchange=exchange).compile()
    # per chip: at least its six (m, k, p) f32 ADMM state blocks
    assert _fits_one_chip(compiled) > 6 * part.shard_size * k * p * 4


def _pallas_cases(sh):
    """(op, impl) -> (callable, abstract args) at widths the v5e compiler
    accepts (each impl's docstring states its VMEM bound)."""
    S = functools.partial(_abstract, sh)
    i32 = jnp.int32

    def make(op, impl):
        return dispatch._REGISTRY[op][impl].make(False)

    nk, p, m = 1024 * 8, 64, 256
    slots = S((1024, 8, p))
    E = 256
    q = S((1, 1024, 8, 128), jnp.bfloat16)
    return {
        ("mix", "pallas"): (make("mix", "pallas"),
                            (S((16, 4096)), S((16, 4096)), S((16, 16)),
                             S((16,)))),
        ("sparse_mix", "pallas_sparse"): (
            make("sparse_mix", "pallas_sparse"),
            (S((4096, p)), S((4096, 8), i32), S((4096, 8)), S((4096,)),
             S((4096, p)))),
        ("round_step", "pallas"): (
            make("round_step", "pallas"),
            (S((1024, p)), S((nk, p + 1)), S((1024,), jnp.bool_), S((m, p)),
             S((m,), i32), S((m,), i32), S((m, p)), S((1024, p)),
             S((nk,)))),
        ("cl_edge_step", "pallas"): (
            functools.partial(make("cl_edge_step", "pallas"), rho=1.0),
            (S((1024, p)), slots, slots, slots, slots, slots,
             *[S((E, p))] * 6, S((E,), i32), S((E,), i32), S((E,), i32),
             S((E,), i32), S((E,), jnp.bool_), S((E,), jnp.bool_))),
        ("attention", "pallas"): (make("attention", "pallas"), (q, q, q)),
    }


def _registered_pallas():
    return {(op, impl) for op in dispatch.ops()
            for impl, entry in dispatch._REGISTRY[op].items()
            if entry.pallas and not entry.interpret_only}


def test_every_pallas_impl_has_a_compile_case(one_chip):
    covered = set(_pallas_cases(one_chip)) | {
        ("sparse_mix", "pallas_sparse_sharded")}
    assert _registered_pallas() == covered


@pytest.mark.parametrize("case", [
    ("mix", "pallas"), ("sparse_mix", "pallas_sparse"),
    ("round_step", "pallas"), ("cl_edge_step", "pallas"),
    ("attention", "pallas")], ids=lambda c: "/".join(c))
def test_pallas_impl_compiles_for_v5e(one_chip, case):
    fn, args = _pallas_cases(one_chip)[case]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_pallas_sparse_mix_compiles_for_v5e_mesh(topo):
    mesh = Mesh(topo.devices, ("shards",))
    rows = NamedSharding(mesh, P("shards"))
    S = functools.partial(_abstract, rows)
    inner = functools.partial(_sm.sparse_gather_mix, interpret=False)
    fn = functools.partial(_sh.sharded_sparse_mix, inner=inner, mesh=mesh)
    n, k, p = 4096, 8, 64
    compiled = jax.jit(fn).lower(S((n, p)), S((n, k), jnp.int32),
                                 S((n, k)), S((n,)), S((n, p))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
