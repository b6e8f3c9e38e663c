"""The program names its phases: device scopes in the compiled program,
host spans in a process-wide table (``repro.obs``)."""

import re
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import plan_pfft
from repro.plan.config import PlanConfig

N = 256
_ENTRY_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \S+ ([\w-]+)\(")


def entry_instructions(hlo_text):
    """``[(name, opcode)]`` of the entry computation's instructions."""
    body = hlo_text[hlo_text.index("ENTRY"):].split("\n}")[0]
    return [m.groups() for m in map(_ENTRY_INSTR.match, body.splitlines())
            if m]


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "plain"])
def test_planned_path_names_every_instruction(fused):
    plan = plan_pfft(N, method="lb", p=4,
                     config=PlanConfig(radix=4, fused=fused))
    found = plan.scope_map()
    text = plan._fn.lower(plan.input_spec()).compile().as_text()
    unnamed = [(name, op) for name, op in entry_instructions(text)
               if op not in ("parameter", "tuple") and found[name] is None]
    assert unnamed == []
    assert {obs.SPLIT, obs.ROWFFT, obs.JOIN} <= set(found.values())
    assert plan.scope_map() is found  # computed once per plan


def test_scopes_change_no_result():
    x = (np.random.default_rng(1).standard_normal((N, N))
         + 1j * np.random.default_rng(2).standard_normal((N, N)))
    plan = plan_pfft(N, method="lb", p=4, config=PlanConfig(radix=4))
    out = np.asarray(plan.execute(jnp.asarray(x, jnp.complex64)))
    ref = np.fft.fft2(x)
    assert np.abs(out - ref).max() / np.sqrt(np.mean(np.abs(ref) ** 2)) < 1e-5


def test_all_to_all_is_the_exchange(dist_subprocess):
    dist_subprocess("""
import re
import jax
from repro.core import plan_pfft
from repro.launch.mesh import make_fft_mesh
from repro.plan.config import PlanConfig
mesh = make_fft_mesh(4)
for cfg in (PlanConfig(), PlanConfig(radix=4, pipeline_panels=2),
            PlanConfig(radix=4, fused=True)):
    plan = plan_pfft(256, method="lb", mesh=mesh, config=cfg)
    found = plan.scope_map()
    text = plan._fn.lower(plan.input_spec()).compile().as_text()
    a2a = re.findall(r"^\\s*(?:ROOT\\s+)?%?([\\w.\\-]+) = [^=]*? all-to-all\\(",
                     text, re.M)
    assert a2a, cfg.describe()
    assert {found[name] for name in a2a} == {"pfft.exchange"}, (
        cfg.describe(), {name: found[name] for name in a2a})
print("OK")
""")


HLO = """\
HloModule jit_raw, is_scheduled=true

%fused_join (param_0: f32[8], param_1: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %param_1 = f32[8]{0} parameter(1)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_1), metadata={op_name="jit(raw)/pfft.join/add"}
}

ENTRY %main (m.1: c64[8]) -> c64[8] {
  %m.1 = c64[8]{0} parameter(0), metadata={op_name="m"}
  %custom-call.1 = f32[8]{0} custom-call(%m.1), custom_call_target="X64SplitLow", metadata={op_name="m"}
  %constant.3 = f32[8]{0} constant({...}), metadata={op_name="jit(raw)/jit(fft_rows_op)"}
  %copy.2 = f32[8]{0} copy(%constant.3)
  %fft_rows_op.2 = f32[8]{0} custom-call(%custom-call.1, %copy.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(raw)/pfft.rowfft/jit(fft_rows_op)/pallas_call"}
  %transpose.4 = f32[8]{0} transpose(%fft_rows_op.2), metadata={op_name="jit(raw)/pfft.rowfft/jit(fft_rows_op)/pfft.split/transpose"}
  %stray.5 = f32[8]{0} negate(%transpose.4), metadata={op_name="jit(raw)/neg"}
  %fusion.6 = f32[8]{0} fusion(%stray.5, %fft_rows_op.2), kind=kLoop, calls=%fused_join, metadata={op_name="jit(raw)/pfft.join/add"}
  ROOT %custom-call.7 = c64[8]{0} custom-call(%fusion.6), custom_call_target="X64Combine", metadata={op_name="jit(raw)/transpose"}
}
"""


def test_scope_map_rule():
    found = obs.scope_map(HLO)
    assert found["custom-call.1"] == obs.SPLIT      # X64 split, by target
    assert found["custom-call.7"] == obs.JOIN       # X64 combine
    assert found["fft_rows_op.2"] == obs.ROWFFT
    assert found["transpose.4"] == obs.SPLIT        # innermost scope wins
    assert found["fusion.6"] == obs.JOIN
    assert found["add.1"] == obs.JOIN               # inside the fusion
    assert found["stray.5"] is None                 # program work, unnamed
    assert found["m.1"] is None and found["param_0"] is None
    # made by the compiler: takes the scope its users share
    assert found["copy.2"] == obs.ROWFFT
    assert found["constant.3"] == obs.ROWFFT


class _FakePlan:
    def __init__(self, found):
        self.found = found

    def scope_map(self):
        return self.found


def test_live_scope_map_merges_and_forgets_dead_plans():
    a = _FakePlan({"x.1": obs.SPLIT, "y.1": obs.JOIN})
    b = _FakePlan({"x.1": obs.SPLIT, "y.1": obs.ROWFFT, "z.1": None})
    obs.register(a)
    obs.register(b)
    merged = obs.live_scope_map()
    assert merged["x.1"] == obs.SPLIT
    assert merged["y.1"] is None                    # two plans disagree
    assert merged["z.1"] is None
    del b
    assert obs.live_scope_map()["y.1"] == obs.JOIN
    del a
    assert "x.1" not in obs.live_scope_map()


def test_plans_register_themselves():
    plan = plan_pfft(N, method="lb", p=2)
    assert any(p is plan for p in obs._LIVE.values())
    swapped = plan.with_schedule(plan.schedule)
    assert any(p is swapped for p in obs._LIVE.values())


def test_span_records_count_total_first_and_nests():
    obs.reset()
    with obs.span("outer"):
        for _ in range(3):
            with obs.span("inner"):
                time.sleep(0.002)
    got = obs.snapshot()
    assert got["inner"]["count"] == 3
    assert got["outer"]["count"] == 1
    assert got["inner"]["first_s"] >= 0.002
    assert got["inner"]["total_s"] >= 3 * 0.002 - 1e-9
    assert got["inner"]["total_s"] >= got["inner"]["first_s"]
    assert got["outer"]["total_s"] >= got["inner"]["total_s"]
    got["inner"]["count"] = 99                      # a copy
    assert obs.snapshot()["inner"]["count"] == 3
    obs.reset()
    assert obs.snapshot() == {}


def test_span_records_when_the_body_raises():
    obs.reset()
    with pytest.raises(ValueError):
        with obs.span("failing"):
            raise ValueError("boom")
    assert obs.snapshot()["failing"]["count"] == 1


def test_plan_lifecycle_spans():
    obs.reset()
    plan = plan_pfft(N, method="lb", p=4, tune="estimate")
    x = jnp.ones((N, N), jnp.complex64)
    plan.execute(x).block_until_ready()
    plan.execute(x[None]).block_until_ready()       # batched: one span too
    got = obs.snapshot()
    assert got["pfft.plan.partition"]["count"] == 1
    assert got["pfft.plan.schedule"]["count"] == 1
    assert got["pfft.execute"]["count"] == 2
    with pytest.raises(ValueError):
        plan.execute(jnp.ones((N, N + 1), jnp.complex64))
    assert obs.snapshot()["pfft.execute"]["count"] == 2


def test_other_plan_types_execute_in_a_span():
    from repro.core.api import plan_pfft1_large, plan_pfft3
    obs.reset()
    p3 = plan_pfft3(16)
    p3.execute(jnp.ones((16, 16, 16), jnp.complex64)).block_until_ready()
    p1 = plan_pfft1_large(1024)
    p1.execute(jnp.ones((1024,), jnp.complex64)).block_until_ready()
    assert obs.snapshot()["pfft.execute"]["count"] == 2
    for plan in (p3, p1):
        found = plan.scope_map()
        assert obs.ROWFFT in set(found.values())


A2A_HLO = """\
HloModule jit_raw, is_scheduled=true, num_partitions=4

ENTRY %main (x.1: f32[64,4,32]) -> f32[64,4,32] {
  %x.1 = f32[64,4,32]{2,1,0} parameter(0)
  %all_to_all.2 = f32[64,4,32]{2,0,1:T(8,128)} all-to-all(%x.1), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={1}, metadata={op_name="jit(raw)/shard_map/pfft.exchange/all_to_all"}
  %all-to-all.3 = (c64[16,8]{1,0}, c64[16,8]{1,0}, c64[16,8]{1,0}, c64[16,8]{1,0}) all-to-all(%a, %b, %c, %d), channel_id=2, replica_groups=[1,4]<=[4]
  %all-to-all-start.4 = ((f32[8,8]{1,0}), f32[8,8]{1,0}) all-to-all-start(%x.1), replica_groups={{0,1},{2,3}}
  %all-to-all-done.4 = f32[8,8]{1,0} all-to-all-done(%all-to-all-start.4)
  %all-to-all.5 = f32[16]{0} all-to-all(%y), dimensions={0}
  %gte.6 = c64[16,8]{1,0} get-tuple-element(%all-to-all.3), index=0
  ROOT %copy.7 = f32[64,4,32]{2,1,0} copy(%all_to_all.2)
}
"""


def test_exchange_counts_read_every_form_of_all_to_all():
    got = obs.exchange_counts(A2A_HLO)
    sync = 64 * 4 * 32 * 4 * 3 // 4         # f32, 3 of 4 parts leave
    tup = 4 * 16 * 8 * 8 * 3 // 4           # c64 tuple, iota groups of 4
    async_ = 8 * 8 * 4 // 2                 # counted at its -done, pairs
    default = 16 * 4 * 3 // 4               # no groups: num_partitions
    assert got == {obs.COLLECTIVES: 4,
                   obs.EXCHANGE_BYTES: sync + tup + async_ + default}
    assert obs.exchange_counts(HLO) == {}


def test_counter_table_records_reads_and_resets():
    obs.reset()
    obs.count("a", 3)
    obs.count("a", 5)                       # the latest stands
    got = obs.counters()
    assert got == {"a": 5}
    got["a"] = 99                           # a copy
    assert obs.counters() == {"a": 5}
    with obs.span("s"):
        pass
    obs.reset()
    assert obs.counters() == {} and obs.snapshot() == {}


class _CountingPlan(_FakePlan):
    def __init__(self, counts):
        super().__init__({})
        self.counts = counts

    def counters(self):
        return self.counts


def test_live_counters_ask_every_live_plan():
    obs.reset()
    plain = _FakePlan({})                   # no counters at all
    counting = _CountingPlan({obs.EXCHANGE_BYTES: 7})
    obs.register(plain)
    obs.register(counting)
    assert obs.counters() == {}             # nothing recorded yet
    assert obs.live_counters()[obs.EXCHANGE_BYTES] == 7
    obs.reset()
    assert obs.live_counters()[obs.EXCHANGE_BYTES] == 7
    del plain, counting


def test_one_chip_plan_has_no_exchange_counters():
    obs.reset()
    plan = plan_pfft(N, method="lb", p=4, config=PlanConfig(radix=4))
    assert plan.counters() == {}
    assert obs.counters() == {}


TILES_HLO = """\
HloModule jit_raw, is_scheduled=true

ENTRY %main (x.1: f32[8192,4,1024]) -> f32[8192,4,8,1024] {
  %x.1 = f32[8192,4,1024]{2,1,0:T(8,128)} parameter(0)
  %copy.2 = f32[8192,4,1,1024]{3,2,1,0:T(1,128)} copy(%x.1), metadata={op_name="jit(raw)/shard_map/pfft.transpose/broadcast_in_dim"}
  %copy.3 = f32[8192,4,8,1024]{3,2,1,0:T(8,128)} copy(%x.1), metadata={op_name="jit(raw)/shard_map/pfft.transpose/transpose"}
  %copy.4 = f32[8,4,1,1024]{3,2,1,0:T(1,128)} copy(%x.1), metadata={op_name="jit(raw)/shard_map/pfft.transpose/reshape"}
  %copy.5 = f32[8192,4,1,1024]{3,2,1,0:T(1,128)} copy(%x.1), metadata={op_name="jit(raw)/neg"}
  %fusion.6 = (f32[8192,4,1,1024]{3,2,1,0:T(1,128)}, f32[8]{0}) fusion(%x.1), kind=kLoop, calls=%f, metadata={op_name="jit(raw)/pfft.exchange/all_to_all"}
  %fusion.7 = (f32[8192,4,1,1024]{3,2,1,0:T(1,128)}, token[]) fusion(%x.1), kind=kLoop, calls=%g, metadata={op_name="jit(raw)/pfft.exchange/all_to_all"}
  ROOT %tuple.8 = (f32[8192,4,8,1024]{3,2,1,0:T(8,128)}) tuple(%copy.3)
}
"""


def test_sparse_tile_counts_read_one_row_tiles_of_named_arrays():
    """A result of 1 MiB or more, tiled one row high, inside a ``pfft.*``
    scope counts, whatever else its result holds; a dense tile, a small
    array or an unnamed op does not; a text with no tiled layout has no
    counter."""
    def counts(text):
        return obs.sparse_tile_counts(text, obs.scope_map(text))
    assert counts(TILES_HLO) == {obs.SPARSE_TILES: 3}  # copy.2, fusion.6/7
    dense = TILES_HLO.replace("T(1,128)", "T(8,128)")
    assert counts(dense) == {obs.SPARSE_TILES: 0}
    assert counts(HLO) == {}                        # CPU text: no tiles
