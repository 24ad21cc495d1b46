"""The names the program writes into a profiler trace (docs/observability.md,
"Names on the profiler's clock"): ``hvd_*`` scopes in a compiled step's
``op_name``s, ``hvd_*`` host spans on the eager spine, and the rule that
every such name starts with ``hvd_``.  All on the CPU: names and their
nesting, never a time."""

import ast
import glob
import inspect
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import common, run, trace_reduce  # noqa: E402
from benchmark import traffic as traffic_gen  # noqa: E402
from benchmark.families import gpt  # noqa: E402
from horovod_tpu.ops import embedding, grouped_matmul  # noqa: E402


def _lowered_gpt_step(hvd, **tx_options) -> str:
    """The ``shard_map`` step of ``benchmark/families/gpt.py:build`` (its
    lines, with the optimizer's options open) at the configuration's
    rehearsal sizes on four virtual devices, lowered with debug
    information: each op's ``loc("<op_name>")``."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from horovod_tpu import models

    cfg = run.load_json("configs", "gpt2-medium.json")
    traffic = traffic_gen.resolve(
        run.load_json("traffic", "fixed-batch-8x1024x4.json"), rehearse=True)
    mesh = common.hvd_mesh(jax.devices()[:4])
    cell = gpt.setup(cfg, mesh, seed=3, rehearse=True)
    (ids,), = traffic_gen.make_batches(traffic, gpt.inputs(cell, traffic),
                                       mesh, seed=3)
    model = cell["model"]
    tx = hvd.DistributedOptimizer(
        common.make_optimizer(cfg["optimizer"]), axis_name="hvd",
        **tx_options)

    def train_step(params, ids):
        # The state is made inside the step, so that the sharded optimizer's
        # per-chip chunks need no out_specs of their own.
        opt_state = tx.init(params)
        loss, grads = jax.value_and_grad(
            lambda p: models.lm_loss(model.apply(p, ids), ids))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates),
                hvd.allreduce(loss, axis_name="hvd"))

    step = jax.jit(shard_map(train_step, mesh=mesh, in_specs=(P(), P("hvd")),
                             out_specs=(P(), P())))
    return step.lower(cell["params"], ids).as_text(debug_info=True)


def _op_names(lowered: str) -> list:
    """Every ``op_name`` of the module's debug locations (inside the
    ``shard_map`` body they are relative to it: ``hvd_update/mul``)."""
    return re.findall(r'loc\("([^"]+)"', lowered)


UPDATE = re.compile(r"(^|/)hvd_update/")
EXCHANGE = re.compile(r"(^|/)hvd_exchange/")


def test_compiled_step_names_its_update(hvd_single):
    names = _op_names(_lowered_gpt_step(hvd_single))
    update = [n for n in names if UPDATE.search(n)]
    # AdamW's arithmetic, all of it under the scope and outside the model's
    # passes: moments, bias corrections, the decayed update.
    assert {n.rsplit("/", 1)[1] for n in update} >= {"mul", "add", "sqrt",
                                                     "div"}
    assert not any("jvp(" in n or "transpose(" in n for n in update)
    # apply_updates is the user's line, not the optimizer's.
    assert "add" in names


def test_sharded_step_names_its_exchange_and_its_shard_update(hvd_single):
    names = _op_names(_lowered_gpt_step(hvd_single,
                                        shard_optimizer_states=True))
    for collective in ("reduce_scatter", "all_gather_invariant"):
        found = [n for n in names if n.endswith("/" + collective)]
        assert found, (collective, sorted({n.rsplit("/", 1)[1]
                                           for n in names}))
        assert all(EXCHANGE.search(n) for n in found), found
    assert any(UPDATE.search(n) and n.endswith("/sqrt") for n in names)


def _profile_eager_update(hvd, out_dir):
    """One eager ``tx.update`` over three leaves under the profiler (the
    harness's options), after one outside it.  Returns the ``.xplane.pb``."""
    from jax.profiler import TraceAnnotation

    params = {"w": jnp.ones((64, 64)), "b": jnp.ones((64,)),
              "s": jnp.ones((8,))}
    grads = jax.tree_util.tree_map(lambda p: 0.5 * p, params)
    tx = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                  op=hvd.Average)
    state = tx.init(params)
    _, state = tx.update(grads, state, params)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(out_dir), profiler_options=options)
    try:
        with TraceAnnotation("bench_window"):
            updates, state = tx.update(grads, state, params)
            jax.block_until_ready(updates)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(out_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(found) == 1, found
    return found[0]


def test_a_state_space_step_names_its_projections_mix_scan_and_head():
    """``models/jamba.py``'s scopes in the lowered step of
    ``benchmark/families/jamba.py`` at the configuration's rehearsal sizes:
    ``hvd_ssm_proj`` holds the mixer's two large products, ``hvd_ssm_mix``
    the convolution, ``x_proj``, the norms, the softplus and the gate,
    ``hvd_ssm_scan`` the scan (off the TPU its ``lax.scan`` form; on it the
    kernels ``hvd_ssm_scan_fwd`` / ``_bwd``), ``hvd_lm_head`` the final norm
    and the tied head; both passes carry them."""
    from benchmark.families import jamba
    from horovod_tpu.models import jamba as model_jamba

    cfg = run.load_json("configs", "jamba2-3b-tp4.json")
    traffic = traffic_gen.resolve(
        run.load_json("traffic", "jamba-causal-1x16384x1.json"),
        rehearse=True)
    mesh = common.hvd_mesh(jax.devices()[:1])
    cell = jamba.setup(cfg, mesh, seed=3, rehearse=True)
    (ids,), = traffic_gen.make_batches(traffic, jamba.inputs(cell, traffic),
                                       mesh, seed=3)
    lowered = jax.jit(jax.grad(lambda v: model_jamba.lm_loss(
        cell["model"], v, ids))).lower(cell["params"]).as_text(
            debug_info=True)
    names = _op_names(lowered)

    def under(scope, op, backward=False):
        return any(f"/{scope}/" in n and n.endswith(op)
                   and ("transpose(" in n) == backward for n in names)

    for backward in (False, True):
        assert under("hvd_ssm_proj", "dot_general", backward)
        assert under("hvd_ssm_mix", "dot_general", backward)   # x_proj, dt
        assert under("hvd_ssm_mix", "jit(silu)", backward)
        assert under("hvd_ssm_mix", "jit(softplus)", backward)
        assert under("hvd_ssm_scan", "", backward)
        assert under("hvd_lm_head", "", backward)
    # The scan's arithmetic is under its own scope, not under the mix's.
    scan = [n for n in names if "/hvd_ssm_scan/" in n]
    assert scan and not any("/hvd_ssm_mix/" in n for n in scan)
    # The attention block (index 2 of 4 here) has none of the four but the
    # head's.
    assert not any("layer_2" in n and "hvd_ssm" in n for n in names)


def test_a_laguna_step_names_what_its_layer_kinds_add():
    """``models/laguna.py``'s scopes in the lowered step of
    ``benchmark/families/laguna.py`` at the configuration's rehearsal sizes:
    ``hvd_attn_proj`` holds the q, k, v and output products, ``hvd_rope`` the
    rotary turn, ``hvd_attn_gate`` the gate's product, its sigmoid and the
    0/1 product that spreads it, ``hvd_moe_shared`` the shared expert beside
    ``parallel/moe.py``'s ``hvd_moe_route`` and ``hvd_moe_experts``,
    ``hvd_lm_head`` the final norm, the head of its own and the loss (through
    ``losses.head_cross_entropy``); both passes carry them, and the dense
    layer has none of the mixture's."""
    from benchmark.families import laguna
    from horovod_tpu.models import laguna as model_laguna

    cfg = run.load_json("configs", "laguna-s-2.1-ep32.json")
    traffic = traffic_gen.resolve(
        run.load_json("traffic", "laguna-causal-1x16384x1.json"),
        rehearse=True)
    mesh = common.hvd_mesh(jax.devices()[:1])
    cell = laguna.setup(cfg, mesh, seed=3, rehearse=True)
    (ids,) = traffic_gen.make_batches(traffic, laguna.inputs(cell, traffic),
                                      mesh, seed=3)[0]
    names = _op_names(jax.jit(jax.grad(lambda v: model_laguna.lm_loss(
        cell["model"], v, ids))).lower(cell["params"]).as_text(
            debug_info=True))

    def under(scope, op, backward=False):
        # The expert layer's backward is a custom_vjp's: its ops carry the
        # scope inside transpose(jvp(...)).
        return any(re.search(rf"[/(]{scope}[/)]", n) and n.endswith(op)
                   and ("transpose(" in n) == backward for n in names)

    for backward in (False, True):
        assert under("hvd_attn_proj", "dot_general", backward)
        assert under("hvd_rope", "mul", backward)
        assert under("hvd_attn_gate", "dot_general", backward)
        assert under("hvd_moe_route", "", backward)
        assert under("hvd_moe_experts", "", backward)
        assert under("hvd_moe_shared", "dot_general", backward)
        # The blocked head's three products lie in its scan's body, whose
        # names are relative to it here; its reverse mode is by hand, and the
        # backward scales what the forward made.
        assert under("hvd_lm_head", "", backward)
    assert under("hvd_lm_head", "while")
    assert under("hvd_attn_gate", "logistic")
    # The rotary tables are made under the turn's scope, not the products'.
    assert not any("/hvd_attn_proj/" in n and n.endswith(("cos", "sin"))
                   for n in names)
    # Layer 0 is the dense one: nothing of the mixture's is in it.
    assert not any("layer_0" in n and "hvd_moe" in n for n in names)
    assert any("layer_1" in n and "hvd_moe_shared" in n for n in names)


def test_a_joyai_step_names_what_latent_attention_adds():
    """``models/joyai.py``'s scopes in the lowered step at the
    configuration's rehearsal sizes: ``hvd_attn_proj`` holds the five latent
    projections, ``hvd_mla_latent`` the two latent norms, ``hvd_rope`` the
    rotary turn of q_rope and of the one k_rope, ``hvd_moe_route`` the
    sigmoid router with ``parallel/moe.py``'s dispatch, ``hvd_moe_shared``
    the shared expert, ``hvd_lm_head`` the final norm, the untied head and the
    loss; both passes carry them, and the dense layer has none of the
    mixture's."""
    from benchmark.families import joyai
    from horovod_tpu.models import joyai as model_joyai

    cfg = run.load_json("configs", "joyai-llm-flash-ep16.json")
    traffic = traffic_gen.resolve(
        run.load_json("traffic", "joyai-causal-1x16384x1.json"),
        rehearse=True)
    mesh = common.hvd_mesh(jax.devices()[:1])
    cell = joyai.setup(cfg, mesh, seed=3, rehearse=True)
    (ids,) = traffic_gen.make_batches(traffic, joyai.inputs(cell, traffic),
                                      mesh, seed=3)[0]
    rest = {"balancing": cell["params"]["balancing"]}
    names = _op_names(jax.jit(jax.grad(lambda p: model_joyai.lm_loss(
        cell["model"], {**rest, "params": p}, ids))).lower(
            cell["params"]["params"]).as_text(debug_info=True))

    def under(scope, op, backward=False):
        return any(re.search(rf"[/(]{scope}[/)]", n) and n.endswith(op)
                   and ("transpose(" in n) == backward for n in names)

    for backward in (False, True):
        assert under("hvd_attn_proj", "dot_general", backward)
        assert under("hvd_mla_latent", "mul", backward)
        assert under("hvd_rope", "mul", backward)
        assert under("hvd_moe_route", "", backward)
        assert under("hvd_moe_experts", "", backward)
        assert under("hvd_moe_shared", "dot_general", backward)
        # The blocked head's three products lie in its scan's body, whose
        # names are relative to it here; its reverse mode is by hand, and the
        # backward scales what the forward made.
        assert under("hvd_lm_head", "", backward)
    assert under("hvd_lm_head", "while")
    assert under("hvd_moe_route", "logistic")
    for kernel in ("q_a", "q_b_nope", "q_b_rope", "kv_a", "kv_b", "o_proj"):
        assert any(f"/hvd_attn_proj/{kernel}/" in n for n in names), kernel
    # The rotary tables are made under the turn's scope, not the products'.
    assert not any("/hvd_attn_proj/" in n and n.endswith(("cos", "sin"))
                   for n in names)
    # Layer 0 is the dense one: nothing of the mixture's is in it.
    assert not any("layer_0" in n and "hvd_moe" in n for n in names)
    assert any("layer_1" in n and "hvd_moe_shared" in n for n in names)


def test_a_sala_step_names_what_both_mixers_add():
    """``models/sala.py``'s scopes in the lowered step at the configuration's
    rehearsal sizes: ``hvd_attn_proj`` holds both mixers' five projections,
    ``hvd_lightning_prep`` the lightning layers' head norms, rotary turn and
    slopes, ``hvd_qk_norm`` the sparse layer's head norms, ``hvd_attn_gate``
    both gates and the lightning output norm, ``hvd_sparse_select`` the
    selection (forward only: it passes no gradient), ``hvd_mlp`` the SwiGLU,
    ``hvd_lm_head`` the final norm, the untied head and the loss."""
    from benchmark.families import sala
    from horovod_tpu.models import sala as model_sala

    cfg = run.load_json("configs", "minicpm-sala-tp4.json")
    traffic = traffic_gen.resolve(
        run.load_json("traffic", "sala-causal-1x16384x1.json"),
        rehearse=True)
    mesh = common.hvd_mesh(jax.devices()[:1])
    cell = sala.setup(cfg, mesh, seed=3, rehearse=True)
    (ids,) = traffic_gen.make_batches(traffic, sala.inputs(cell, traffic),
                                      mesh, seed=3)[0]
    names = _op_names(jax.jit(jax.grad(lambda v: model_sala.lm_loss(
        cell["model"], v, ids))).lower(cell["params"]).as_text(
            debug_info=True))

    def under(scope, op, backward=False):
        return any(re.search(rf"[/(]{scope}[/)]", n) and n.endswith(op)
                   and ("transpose(" in n) == backward for n in names)

    for backward in (False, True):
        assert under("hvd_attn_proj", "dot_general", backward)
        assert under("hvd_lightning_prep", "mul", backward)
        assert under("hvd_qk_norm", "mul", backward)
        assert under("hvd_attn_gate", "logistic" if not backward else "mul",
                     backward)
        assert under("hvd_mlp", "dot_general", backward)
        assert under("hvd_lm_head", "", backward)
    # (The selection's tiles run in a ``lax.map``, whose body's names are
    # relative to it here: the compressed keys' mean is made outside it.)
    assert under("hvd_sparse_select", "div")
    assert not under("hvd_sparse_select", "", backward=True)
    for kernel in ("q_proj", "k_proj", "v_proj", "gate_proj", "o_proj"):
        assert any(f"/hvd_attn_proj/{kernel}/" in n for n in names), kernel
    # The sparse layer turns nothing and has no slopes; the lightning layers
    # choose nothing.
    assert not any("layer_0" in n and "hvd_lightning_prep" in n
                   for n in names)
    assert not any("layer_1" in n and "hvd_sparse_select" in n
                   for n in names)


# One cell a family, and the layers its step must show in both passes
# (``hvd_embed`` and a head besides, checked for every family).
FAMILY_CELLS = {
    "gpt": ("gpt2m-1chip", {"hvd_block", "hvd_attn", "hvd_attn_proj",
                            "hvd_mlp", "hvd_lm_head"}),
    "bert": ("bert-large-s512", {"hvd_block", "hvd_attn", "hvd_attn_proj",
                                 "hvd_mlp", "hvd_mlm_head", "hvd_nsp_head"}),
    "resnet": ("resnet50-1chip", {"hvd_stem", "hvd_block", "hvd_head"}),
    "sdar": ("sdar-moe-ep8-s4096", {
        "hvd_block", "hvd_attn", "hvd_attn_proj", "hvd_moe_route",
        "hvd_moe_experts", "hvd_lm_head"}),
    "zaya": ("zaya1-moe-ep2-s16384", {
        "hvd_block", "hvd_attn", "hvd_attn_proj", "hvd_cca_mix",
        "hvd_moe_router", "hvd_moe_experts", "hvd_lm_head"}),
    "jamba": ("jamba2-ssm-tp4-s16384", {
        "hvd_block", "hvd_attn", "hvd_attn_proj", "hvd_mlp", "hvd_ssm_proj",
        "hvd_ssm_mix", "hvd_ssm_scan", "hvd_lm_head"}),
    "laguna": ("laguna-swa-ep32-s16384", {
        "hvd_block", "hvd_attn", "hvd_attn_proj", "hvd_mlp", "hvd_rope",
        "hvd_moe_shared", "hvd_lm_head"}),
    "joyai": ("joyai-mla-ep16-s16384", {
        "hvd_block", "hvd_attn", "hvd_attn_proj", "hvd_mlp",
        "hvd_mla_latent", "hvd_moe_shared", "hvd_lm_head"}),
    "sala": ("sala-sparse-linear-tp4-s16384", {
        "hvd_block", "hvd_attn", "hvd_attn_proj", "hvd_mlp",
        "hvd_qk_norm_rope", "hvd_qk_norm", "hvd_attn_gate", "hvd_lm_head"}),
    "phi4flash": ("phi4flash-sambay-tp2-s16384", {
        "hvd_block", "hvd_attn", "hvd_attn_proj", "hvd_attn_diff", "hvd_gmu",
        "hvd_mlp", "hvd_ssm_proj", "hvd_ssm_mix", "hvd_ssm_scan",
        "hvd_lm_head"}),
}
# Ops of a step's forward or backward that no scope of the program's can
# name, each with its reason.
UNNAMED_BY_DESIGN = (
    # jax.checkpoint's own barrier round a checkpointed block: ``nn.remat``
    # wraps the block from outside its ``__call__``, where ``hvd_block`` is.
    re.compile(r"/remat2$"),
)


def _step_op_names(hvd, cell_name: str) -> list:
    """Every ``op_name`` of the family's own compiled step
    (``families/<family>.py:build``) at the configuration's rehearsal sizes
    on one device: the ops inside a fusion keep theirs."""
    entry = run.cell_entry(run.load_spec(), cell_name)
    cfg = run.load_json("configs", entry["config"] + ".json")
    traffic = traffic_gen.resolve(
        run.load_json("traffic", entry["traffic"] + ".json"), rehearse=True)
    family = run.load_family(cfg["family"])
    mesh = common.hvd_mesh(jax.devices()[:1])
    cell = family.setup(cfg, mesh, seed=3, rehearse=True)
    cell["traffic"] = traffic
    cell["batches"] = traffic_gen.make_batches(
        traffic, family.inputs(cell, traffic), mesh, seed=3)
    step, _ = family.build(cell)
    return re.findall(r'op_name="([^"]+)"', step.as_text())


@pytest.mark.parametrize("family", FAMILY_CELLS)
def test_every_op_of_a_step_s_passes_lies_under_a_layer_scope(hvd_single,
                                                              family):
    """The rule ``benchmark/scope_ledger.py`` reads a step by: every op the
    program's modules trace into a step's forward or backward has an
    ``hvd_*`` segment in its ``op_name``, and the innermost one is its
    layer.  A model added later fails this until its layers are named."""
    from benchmark.scope_ledger import layer_of_scope as layer

    cell_name, layers = FAMILY_CELLS[family]
    names = _step_op_names(hvd_single, cell_name)
    passes = [n for n in names if "jvp(" in n or "transpose(" in n]
    assert len(passes) > 500, len(passes)
    bare = sorted({n for n in passes if layer(n) is None
                   and not any(rx.search(n) for rx in UNNAMED_BY_DESIGN)})
    assert not bare, bare[:20]
    found = {backward: {layer(n) for n in passes
                        if ("transpose(" in n) == backward}
             for backward in (False, True)}
    embed_and_head = {"hvd_stem"} if family == "resnet" else {"hvd_embed"}
    for backward in (False, True):
        assert found[backward] >= layers | embed_and_head, (
            backward, sorted((layers | embed_and_head) - found[backward]))
    # The update is named, and outside the model's passes.
    assert any(layer(n) == "hvd_update" for n in names)
    assert not any(layer(n) == "hvd_update" for n in passes)


@pytest.mark.parametrize("family,module", [("gpt", "attn"),
                                           ("bert", "attention")])
def test_a_plain_flash_call_is_named_for_its_attention_module(monkeypatch,
                                                              family, module):
    """A kernel call's HLO instruction is named for its innermost scope, and
    GPT's ``%attn.N`` / BERT's ``%attention.N`` are what the accepted flash
    metrics select by: ``hvd_attn`` lies outside the flax module and
    ``hvd_attn_proj`` round the products alone, so the three kernels of a
    block lowered for a TPU (here, without one) still end their ``op_name``
    ``/<module>/pallas_call``, under ``hvd_block/hvd_attn``."""
    import dataclasses

    from horovod_tpu import models
    from horovod_tpu.models import bert, gpt

    # The models leave interpret=None, and the dispatch then asks which
    # backend is attached; here that is the CPU, so steer it in the test.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = jax.ShapeDtypeStruct((1, 256, 128), jnp.bfloat16)
    if family == "gpt":
        block = gpt.GPTBlock(models.GPTConfig(
            hidden_size=128, num_heads=2, max_seq_len=256, use_flash=True,
            dtype=jnp.bfloat16))
    else:
        block = bert.TransformerLayer(dataclasses.replace(
            models.BERT_LARGE, hidden_size=128, num_heads=2,
            intermediate_size=256, use_flash=True))
    params = jax.eval_shape(block.init, jax.random.PRNGKey(0), x)
    lowered = jax.jit(jax.grad(lambda p, x: jnp.sum(
        block.apply(p, x).astype(jnp.float32) ** 2))).trace(
            params, x).lower(lowering_platforms=("tpu",)).as_text(
                debug_info=True)
    locations = dict(re.findall(r'^(#loc\d+) = loc\("([^"]+)"', lowered,
                                re.M))
    calls = [locations[ref] for ref in re.findall(
        r'@tpu_custom_call\(.*loc\((#loc\d+)\)$', lowered, re.M)]
    assert len(calls) == 3, calls     # forward, dq, dkv
    for name in calls:
        assert name.endswith(f"/hvd_block/hvd_attn/{module}/pallas_call"), \
            name
    products = [n for n in locations.values() if n.endswith("dot_general")
                and f"/{module}/" in n]
    assert products and all(f"/{module}/hvd_attn_proj/" in n
                            for n in products), products


def test_eager_update_writes_the_spine_s_spans(hvd_single, tmp_path):
    path = _profile_eager_update(hvd_single, tmp_path)
    trace = trace_reduce.read_xplane(path, steps=1)
    spans = {}
    for h in trace.host:
        spans.setdefault(h.name, []).append(h)
    # Bare names: the arguments travel as the event's stats (below).
    # (The dispatcher's host-alive mark is there too if its wait for a
    # response timed out inside the profile.)
    assert set(spans) - {"hvd_alive"} == {
        "bench_window", "hvd_exchange", "hvd_enqueue", "hvd_wait",
        "hvd_execute", "hvd_update"}
    assert [len(spans[n]) for n in ("hvd_exchange", "hvd_enqueue", "hvd_wait",
                                    "hvd_update")] == [1, 3, 3, 1]
    exchange, update = spans["hvd_exchange"][0], spans["hvd_update"][0]
    for inner in spans["hvd_enqueue"] + spans["hvd_wait"]:
        assert exchange.start_ns <= inner.start_ns
        assert inner.end_ns <= exchange.end_ns
    assert exchange.end_ns <= update.start_ns  # exchange, then update
    for pattern in ("^hvd_enqueue$", "^hvd_wait$", "^hvd_execute$",
                    "^hvd_update$"):
        assert trace_reduce.host_span_ms(trace, {}, pattern) > 0

    # Threads and arguments: what ``read_xplane`` does not keep.
    from jax.profiler import ProfileData

    lines = {}   # span name -> {index of the thread's line}
    stats = {}   # span name -> [stats of each event]
    at = 0
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            at += 1
            for e in line.events:
                if e.name.startswith("hvd_"):
                    lines.setdefault(e.name, set()).add(at)
                    stats.setdefault(e.name, []).append(
                        (e.start_ns, dict(e.stats)))
    caller = lines["hvd_exchange"]
    assert len(caller) == 1
    assert lines["hvd_enqueue"] == lines["hvd_wait"] == caller
    assert lines["hvd_update"] == caller
    assert lines["hvd_execute"].isdisjoint(caller)  # the dispatcher's
    handles = sorted(s["handle"] for _, s in stats["hvd_enqueue"])
    assert handles == sorted(s["handle"] for _, s in stats["hvd_wait"])
    assert len(set(handles)) == 3
    # A response carries tensors that were enqueued before it executes:
    # when the j-th hvd_execute starts, at least as many enqueues have
    # started as the responses so far carry tensors.
    enqueued = sorted(t for t, _ in stats["hvd_enqueue"])
    carried = 0
    for start, s in sorted(stats["hvd_execute"], key=lambda kv: kv[0]):
        assert "seq" in s  # -1 with one rank: nothing goes on the wire
        carried += s["tensors"]
        assert sum(t <= start for t in enqueued) >= carried
    assert carried == 3


NAMING_CALLS = {"TraceAnnotation", "named_scope"}


def _callee_names(func) -> set:
    """The names a call's callee may resolve to: ``f``, ``m.f``,
    ``(f if c else g)``."""
    if isinstance(func, ast.Name):
        return {func.id}
    if isinstance(func, ast.Attribute):
        return {func.attr}
    if isinstance(func, ast.IfExp):
        return _callee_names(func.body) | _callee_names(func.orelse)
    return set()


def _names_written(tree) -> list:
    """``(line, name or None)`` of every name a module writes into a trace:
    the first argument of a ``TraceAnnotation`` / ``named_scope`` call and a
    ``pallas_call``'s ``name=``; None where it is not a string literal.  And
    the second argument of a ``checkpoint_name`` call: no trace shows it, a
    checkpoint's policy asks for it, and it goes by the same rule."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _callee_names(node.func)
        if callee & NAMING_CALLS:
            args = node.args[:1] or [k.value for k in node.keywords
                                     if k.arg == "name"]
        elif "checkpoint_name" in callee:
            args = node.args[1:2] or [k.value for k in node.keywords
                                      if k.arg == "name"]
        elif "pallas_call" in callee:
            args = [k.value for k in node.keywords if k.arg == "name"]
        else:
            continue
        for arg in args:
            literal = isinstance(arg, ast.Constant) and isinstance(arg.value,
                                                                   str)
            out.append((node.lineno, arg.value if literal else None))
    return out


def test_every_name_the_program_writes_into_a_trace_starts_with_hvd():
    """``read_xplane`` keeps a host span only if it is named ``bench_*``
    (the harness's) or ``hvd_*`` (the program's), and a reader finds a scope
    or a kernel by that prefix: a name outside it never reaches a metric."""
    seen, bad = 0, []
    for path in glob.glob(os.path.join(REPO, "horovod_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        for line, name in _names_written(tree):
            seen += 1
            if name is None or not re.match(r"^hvd_[a-z0-9_]+$", name):
                bad.append((os.path.relpath(path, REPO), line, name))
    # One kernel is named by its caller: ``_sum_rows`` is ``hvd_moe_sum_rows``
    # for the expert layer and ``embed_grad_sum_rows`` for the embedding's
    # gradient, which is plain on purpose: a kernel named ``hvd_*`` is a layer
    # of its own (``benchmark/scope_ledger.py``), and this one belongs to
    # ``hvd_embed``, whose metrics read it there (test_tpu_compile.py).
    assert [(path, name) for path, _, name in bad] == [
        ("horovod_tpu/ops/grouped_matmul.py", None)], bad
    assert (inspect.signature(grouped_matmul._sum_rows).parameters["name"]
            .default, embedding.KERNEL_NAME) == ("hvd_moe_sum_rows",
                                                 "embed_grad_sum_rows")
    # optimizer.py 8, context.py 4, ops/device_plane.py 3, step_watch.py 2
    assert seen >= 14
    # The rule itself, on a module that breaks it three ways.
    broken = ast.parse(
        "with jax.named_scope('update'): pass\n"
        "with TraceAnnotation(label): pass\n"
        "pl.pallas_call(k, name='flash_fwd')(x)\n"
        "pl.pallas_call(k)(x)\n"
        "with (jax.named_scope if t else TraceAnnotation)('hvd_ok'): pass\n"
        "y = checkpoint_name(x, 'kept')\n")
    assert sorted(_names_written(broken), key=lambda found: found[0]) == [
        (1, "update"), (2, None), (3, "flash_fwd"), (5, "hvd_ok"),
        (6, "kept")]


def _checkpoint_names_given(module) -> list:
    """The literals of a module's ``checkpoint_name`` calls."""
    with open(module.__file__) as f:
        tree = ast.parse(f.read())
    return [node.args[1].value for node in ast.walk(tree)
            if isinstance(node, ast.Call)
            and "checkpoint_name" in _callee_names(node.func)]


@pytest.mark.parametrize("asks", ["flash_attention", "jamba"])
def test_the_names_a_checkpoint_may_keep_are_the_ones_the_kernels_give(asks):
    """``ops/flash_attention.py:CHECKPOINT_NAMES`` is what ZAYA's policy asks
    for, and the literals of that file's ``checkpoint_name`` calls are what
    the forward rule gives; ``models/jamba.py:CHECKPOINT_NAMES`` is what a
    checkpointed ``JambaBlock`` asks for: the literals of its own calls and
    the flash kernel's two.  A policy over a name nothing carries keeps
    nothing, in silence."""
    from horovod_tpu.models import jamba
    from horovod_tpu.ops import flash_attention

    given = _checkpoint_names_given(flash_attention)
    asked = flash_attention.CHECKPOINT_NAMES
    if asks == "jamba":
        given += _checkpoint_names_given(jamba)
        asked = jamba.CHECKPOINT_NAMES
    assert sorted(given) == sorted(asked)
    assert len(given) == {"flash_attention": 2, "jamba": 6}[asks]


@pytest.mark.parametrize("family", ["jamba", "laguna"])
def test_a_name_under_no_checkpoint_lowers_to_nothing(family, monkeypatch):
    """``models/jamba.py`` names the halves of its paired projections for its
    own checkpoint's policy.  A block under no checkpoint (Jamba's with
    ``checkpoint_blocks`` off; every one of Laguna's, which builds
    ``PairedDense`` too and has no switch for one) lowers to the same text
    with the names as with ``checkpoint_name`` an identity."""
    from horovod_tpu import models
    from horovod_tpu.models import jamba, laguna

    module, model = {
        "jamba": (jamba, models.Jamba(models.JAMBA_TINY)),
        "laguna": (laguna, models.Laguna(models.LAGUNA_TINY))}[family]
    ids = jnp.arange(2 * 16, dtype=jnp.int32).reshape(2, 16)
    variables = jax.eval_shape(model.init, jax.random.key(0), ids)

    def lowered() -> str:
        text = jax.jit(jax.value_and_grad(
            lambda v: module.lm_loss(model, v, ids))).lower(
                variables).as_text()
        # A private function's number counts the equations traced before it.
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)

    named = lowered()
    monkeypatch.setattr(jamba, "checkpoint_name", lambda x, name: x)
    assert lowered() == named
    assert "dot_general" in named
