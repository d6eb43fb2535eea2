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


@pytest.mark.parametrize("backend", [
    None, dispatch.ReproBackend.using(round_step="xla")],
    ids=["per_op", "fused_xla"])
def test_mp_scenario_scan_compiles_at_smoke_size(one_chip, backend):
    n, k, p, B = cs.MP_N, MP_KMAX, cs.P_DIM, cs.MP_BATCH
    re_ = cs.RECORD_EVERY
    n_rec = cs.MP_ROUNDS // re_
    S = functools.partial(_abstract, one_chip)
    cond = get_scenario("lossy-10").make_conditions(cs.MP_ROUNDS)
    compiled = engines._scenario_scan.lower(
        _tables(one_chip, n, k), S((n,), jnp.bool_), S((n,)), S((n, p)),
        S((n,)), _keys(one_chip, n_rec, re_),
        S((n_rec,), jnp.int32), conditions=cond, alpha=cs.ALPHA, batch=B,
        record_every=re_, n_rec=n_rec, tel=False,
        backend=backend).compile()
    # the slot table alone is n * k * p * 4 bytes
    assert _fits_one_chip(compiled) > n * k * p * 4


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
    # eight (n, k, p) f32 arrays: five of state, three publish snapshots
    assert _fits_one_chip(compiled) > 8 * n * k * p * 4


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
        mesh, stream, R((Pm, p)), R((Pm, k, p)), R((Pm, k)),
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
            (S((1024, p)), slots, slots, slots, slots, slots, S((1024, p)),
             slots, slots, slots, S((E,), i32), S((E,), i32), S((E,), i32),
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
