"""Compiles for a described TPU v5e (2x2): no chip needed.

Each case lowers a main-path program at real size through the TPU
compiler installed here. A Pallas kernel must come out as Mosaic
(``tpu_custom_call``), not the interpreter: the programs decide interpret
mode from the mesh they are built for, and these meshes are TPU meshes.
The topology is described inside a module fixture — never at import, in
a ``skipif`` or in ``parametrize`` — so every xdist worker collects the
same tests and only the worker given this file loads libtpu.
"""
import os

import numpy as np
import pytest

from ucc_tpu.constants import CollType, DataType, ReductionOp

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding,  # noqa: E402
                          PartitionSpec as P, SingleDeviceSharding)

MIB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # libtpu logs under /tmp else
    # a described-chip compile cannot be read back from a persistent
    # cache without the chip: keep any cache out of these compiles
    old_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", old_cache)
        if old_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = old_log


def _mesh(topo, n, axes=("r",)):
    devs = np.array(topo.devices[:n])
    return Mesh(devs.reshape((1,) * (len(axes) - 1) + (n,)), axes)


def _compile(prog, *shapes):
    return prog.lower(*shapes).compile()


class TestExecutor:
    """The EC reduce kernel (ec/tpu.py) at k=9 sources of 64 MiB f32."""

    @pytest.mark.parametrize("dt,op,alpha", [
        ("float32", ReductionOp.SUM, False),
        ("bfloat16", ReductionOp.MAX, False),
        ("float32", ReductionOp.AVG, True)])
    def test_reduce_kernel(self, topo, dt, op, alpha):
        from ucc_tpu.ec.tpu import _build_reduce_kernel
        k, rows = 9, 131072
        one = SingleDeviceSharding(topo.devices[0])
        kern = _build_reduce_kernel(k, rows, dt, op, alpha, False)
        shapes = [jax.ShapeDtypeStruct((k, rows, 128), jnp.dtype(dt),
                                       sharding=one)]
        if alpha:
            shapes.append(jax.ShapeDtypeStruct((1,), jnp.float32,
                                               sharding=one))
        assert "tpu_custom_call" in _compile(kern, *shapes).as_text()


class TestTlXla:
    """TL/XLA's shard_map programs on a 4-chip mesh at 64 MiB/chip."""

    @pytest.mark.parametrize("coll,hlo", [
        (CollType.ALLREDUCE, "all-reduce"),
        (CollType.ALLTOALL, "all-to-all")])
    def test_program(self, topo, coll, hlo):
        from ucc_tpu import BufferInfo, CollArgs
        from ucc_tpu.tl.xla import _build_xla_program
        n, count = 4, 32 * MIB                  # bf16: 64 MiB per chip
        mesh = _mesh(topo, n)
        args = CollArgs(coll_type=coll,
                        src=BufferInfo(None, count, DataType.BFLOAT16),
                        dst=BufferInfo(None, count, DataType.BFLOAT16),
                        op=ReductionOp.SUM)
        prog, padded = _build_xla_program(
            mesh, n, coll, args, np.dtype(jnp.bfloat16), count, "xla")
        x = jax.ShapeDtypeStruct((n * padded,), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, P("r")))
        assert hlo in _compile(prog, x).as_text()

    @pytest.mark.parametrize("count", [11_547_648, 113_246_208])
    def test_allreduce_flat(self, topo, count):
        """A bf16 AVG allreduce at the size of the first and the last
        gradient bucket of Ouro-2.6B's stage 0 runs on the flat shard:
        the all-reduce reads the entry parameter in its own 1-D layout,
        with no relayout loop, no zero-filled buffer and no temp."""
        from ucc_tpu import BufferInfo, CollArgs
        from ucc_tpu.tl.xla import _build_xla_program
        n = 4
        mesh = _mesh(topo, n)
        args = CollArgs(coll_type=CollType.ALLREDUCE,
                        src=BufferInfo(None, count, DataType.BFLOAT16),
                        dst=BufferInfo(None, count, DataType.BFLOAT16),
                        op=ReductionOp.AVG)
        prog, padded = _build_xla_program(
            mesh, n, CollType.ALLREDUCE, args, np.dtype(jnp.bfloat16), count,
            "xla")
        x = jax.ShapeDtypeStruct((n * padded,), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, P("r")))
        compiled = _compile(prog, x)
        text = compiled.as_text()
        entry = text[text.index("\nENTRY"):].splitlines()
        assert "while(" not in text
        param = next(ln.split("=")[0].strip() for ln in entry
                     if " parameter(0)" in ln)
        assert any(f"all-reduce({param})" in ln for ln in entry)
        assert not any(" broadcast(" in ln and str(count) in ln
                       for ln in entry)
        assert compiled.memory_analysis().temp_size_in_bytes == 0

    @pytest.mark.parametrize("coll,count,dt", [
        (CollType.ALLGATHER, 96_337_920, jnp.bfloat16),
        (CollType.ALLGATHER, 53_896_384, jnp.bfloat16),
        (CollType.REDUCE_SCATTER, 385_351_680, jnp.float32),
        (CollType.REDUCE_SCATTER, 215_585_536, jnp.float32)])
    def test_gather_scatter_flat(self, topo, coll, count, dt):
        """A bf16 allgather and an fp32 AVG reduce-scatter of the
        Olmo-Hybrid-7B FSDP2 units (the embedding and a linear-attention
        block, 4 ranks) run on the flat shard: no relayout loop and no
        temp beyond one buffer of the input's or the output's size."""
        from ucc_tpu import CollArgs
        from ucc_tpu.tl.xla import _build_xla_program
        n = 4
        mesh = _mesh(topo, n)
        args = CollArgs(coll_type=coll, op=ReductionOp.AVG)
        prog, padded = _build_xla_program(
            mesh, n, coll, args, np.dtype(dt), count, "xla")
        assert padded == count
        x = jax.ShapeDtypeStruct((n * padded,), dt,
                                 sharding=NamedSharding(mesh, P("r")))
        compiled = _compile(prog, x)
        text = compiled.as_text()
        assert "while(" not in text
        mem = compiled.memory_analysis()
        # (a buffer padded to whole tiles: under 1 % more)
        assert mem.temp_size_in_bytes <= 1.01 * max(
            mem.argument_size_in_bytes, mem.output_size_in_bytes)


class TestAlltoallv:
    """TL/XLA's alltoallv at the ep4 cell's shapes (DeepSeek-V2-Lite,
    EP=4): 24 576 source rows of 2048 bf16 per chip into a 98 304-row
    destination, the counts as a runtime int32 input."""

    @pytest.mark.parametrize("given", [True, False])
    def test_ragged_body(self, topo, given):
        """One ragged-all-to-all, chosen by platform (no portable
        all-to-all, no conditional); the flat source reaches it through
        bitcasts, with no relayout into rows; the temp is the one
        destination-sized buffer of the result."""
        from ucc_tpu.tl.xla import A2avPlan, _build_a2av_program
        n, w = 4, 2048
        plan = A2avPlan(None, w, 24576 * w, 98304 * w, given)
        mesh = _mesh(topo, n)
        sh = NamedSharding(mesh, P("r"))
        shapes = [jax.ShapeDtypeStruct((n * plan.src_cap,), jnp.bfloat16,
                                       sharding=sh)]
        if given:
            shapes.append(jax.ShapeDtypeStruct((n * plan.dst_cap,),
                                               jnp.bfloat16, sharding=sh))
        shapes.append(jax.ShapeDtypeStruct((n * 4 * n,), jnp.int32,
                                           sharding=sh))
        compiled = _compile(_build_a2av_program(mesh, n, plan, "xla"),
                            *shapes)
        text = compiled.as_text()
        entry = text[text.index("\nENTRY"):].splitlines()
        assert sum("ragged-all-to-all(" in ln for ln in entry) == 1
        assert " all-to-all(" not in text and "conditional(" not in text
        assert not any(" reshape(" in ln and "bf16[" in ln for ln in entry)
        dst_shard = 98304 * w * 2
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert dst_shard <= temp < dst_shard * 1.01


_FAMILIES = ["ring_allreduce", "ring_allgather", "ring_reduce_scatter",
             "bcast", "alltoall", "hbm_allreduce", "hbm_allgather",
             "hbm_reduce_scatter", "hbm_bcast", "hbm_alltoall"]


class TestRingDma:
    """Every tl/ring_dma kernel family compiles as Mosaic at 1 and 4
    chips, each at a count that selects it (kernel_family)."""

    @staticmethod
    def _build(rd, mesh, n, family, nd):
        big = rd.CHUNK_ELEMS * 2
        coll = {"allreduce": CollType.ALLREDUCE,
                "allgather": CollType.ALLGATHER,
                "reduce_scatter": CollType.REDUCE_SCATTER,
                "bcast": CollType.BCAST,
                "alltoall": CollType.ALLTOALL}[family.split("_", 1)[-1]]
        count = (big if family.startswith("hbm_") else 128) * (
            n if coll in (CollType.REDUCE_SCATTER, CollType.ALLTOALL)
            else 1)
        if n > 1 or family not in ("hbm_bcast", "hbm_alltoall"):
            # (a 1-rank team has no ring to pipeline bcast/alltoall over)
            assert rd.kernel_family(coll, count, n, nd) == family
        if family in ("bcast", "hbm_bcast"):
            return rd._BUILDERS[family](mesh, n, 0, nd, count)
        if family.startswith("ring_"):
            return rd.build_ring_program(mesh, n, coll, ReductionOp.SUM,
                                         nd, count)
        if family in ("alltoall", "hbm_alltoall", "hbm_allgather"):
            return rd._BUILDERS[family](mesh, n, nd, count)
        return rd._BUILDERS[family](mesh, n, ReductionOp.SUM, nd, count)

    @pytest.mark.parametrize("n,dt", [(1, "float32"), (4, "float32"),
                                      (4, "bfloat16")])
    @pytest.mark.parametrize("family", _FAMILIES)
    def test_compiles(self, topo, family, n, dt):
        from ucc_tpu.tl import ring_dma as rd
        mesh = _mesh(topo, n)
        nd = np.dtype(jnp.dtype(dt))
        prog, padded = self._build(rd, mesh, n, family, nd)
        x = jax.ShapeDtypeStruct((n * padded,), nd,
                                 sharding=NamedSharding(mesh, P("r")))
        assert "tpu_custom_call" in _compile(prog, x).as_text()


class TestFusedAttention:
    """The fused ring flash-attention kernel (fused_attention.py) shares
    ring_dma's slot/ack protocol. dp_sp compiles the multi-axis path
    (dict MESH device ids over the sp axis of a ('dp', 'sp') mesh)."""

    @pytest.mark.parametrize("axes", [("sp",), ("dp", "sp")])
    def test_compiles(self, topo, axes):
        from ucc_tpu.fused_attention import make_ring_flash_attention
        n, h, s_loc, d = 4, 2, 128, 128
        mesh = _mesh(topo, n, axes)
        prog = make_ring_flash_attention(mesh, causal=True, axis="sp")
        q = jax.ShapeDtypeStruct((h, n * s_loc, d), jnp.bfloat16,
                                 sharding=NamedSharding(
                                     mesh, P(None, "sp", None)))
        assert "tpu_custom_call" in _compile(prog, q, q, q).as_text()


class TestGenDevicePallas:
    """The generated-device Pallas lowering (dsl/lower_device.py) is
    refused by Mosaic — its layer offsets come from an SMEM table that
    Mosaic cannot prove tile-aligned — so a TPU mesh never selects it
    (ROADMAP A4). This flips when the lowering is repaired."""

    def test_refused(self, topo):
        from ucc_tpu.dsl.families import gen_ring
        from ucc_tpu.dsl.lower_device import build_device_program
        n = 4
        prog = gen_ring(n, 1)
        count = 1024 * prog.nchunks * n
        program, padded = build_device_program(
            _mesh(topo, n), prog, n, count, ReductionOp.SUM,
            np.dtype(np.float32), 0, "pallas", 256, "")
        x = jax.ShapeDtypeStruct((n * padded,), jnp.float32,
                                 sharding=NamedSharding(_mesh(topo, n),
                                                        P("r")))
        with pytest.raises(Exception, match="cannot statically prove"):
            _compile(program, x)
