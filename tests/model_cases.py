"""What the sequence models' suites share, written down once.

Every sequence model the zoo builds is held to its benchmark configuration's
plain reference (`benchmark/configs/<config>.reference.py`, which imports
nothing of the program) at one tiny size on the CPU: 2 rows of 32 positions,
hidden 64. A model is a `Case`: its `model_type`, the configuration whose
reference it reads, its tiny file and the keywords of the reference's weight
draw. The suites (`test_seq_*.py` for GLM, `test_lfm2.py`, `test_ling.py`,
`test_evabyte.py`, `test_nemotron_h.py`, `test_smallthinker.py`) stay apart -- `--dist loadfile`
balances by file -- and each calls the three checks every model repeats:

* `check_layer`: one layer of the program against the reference's, a row at
  a time, two weight draws, in one policy;
* `check_loss_and_every_gradient`: the whole net's loss and every stored
  parameter's gradient against the reference's, tolerances passed in;
* `tiny_round` / `program_round` / `check_round`: one tau-round through
  `build_trainer` against the reference's `round_reference`.

What costs time is built once a process and kept (`functools.cache`, as
`round_oracle._step_fn` keeps its jitted step): a reference module, a
model's `CompiledNet`, the program's jitted `value_and_grad` a model and
policy, and the reference's a model -- under ONE `jax.jit`, float32 at
"highest" whatever the policy of the case (taken op by op, a row at a time,
it cost 35 s the first time and 10 s every time after; jitted, 13 s once).

A new model adds its tiny file to `TINY` and its line to `_CONFIGS` here,
and keeps in its own file only what no other model has.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu import precision, zoo
from sparknet_tpu.model.layers import ApplyCtx
from sparknet_tpu.model.net import CompiledNet
from sparknet_tpu.model.seq_layers import IP_OUT
from sparknet_tpu.model.spec import (InputSpec, LayerSpec, MLAttentionParam,
                                     MoEParam, NetSpec, RMSNormParam)
from sparknet_tpu.ops.ssd import CHUNK

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: every model's tiny size: rows a step, positions a row, the stream's width
ROWS, POS, D = 2, 32, 64
#: bf16 against the float32 reference: relative to the result's own scale
BF16_TOL = 0.03
CTX = ApplyCtx(train=True)
SOLVER = {"base_lr": 0.02, "lr_policy": "fixed", "momentum": 0.9,
          "weight_decay": 1e-4}

TINY = {
    #: hidden 64, 2 heads of 16+8 / 16, ranks 24 / 16, 8 experts top-2 of which
    #: 2 are held (experts 2 and 3), vocabulary 256, 32 positions
    "glm4_moe_lite": {
        "model_type": "glm4_moe_lite", "hidden_size": 64, "intermediate_size": 160,
        "moe_intermediate_size": 48, "num_attention_heads": 2, "q_lora_rank": 24,
        "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "n_routed_experts": 2, "n_shared_experts": 1,
        "num_experts_per_tok": 2, "routed_scaling_factor": 1.8,
        "norm_topk_prob": True, "first_k_dense_replace": 1, "num_hidden_layers": 3,
        "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-5, "rope_theta": 1000000,
        "vocab_size": 256, "seq_len": 32, "n_group": 1, "topk_group": 1,
        "share": {"chips_sharing_a_layer": 4, "n_routed_experts": 8,
                  "experts_held": [2, 2], "vocab_rows": [0, 256],
                  "mtp_loss_weight": 0.3}},
    #: hidden 64, 4 query heads of 16 over 2 key/value heads, 3 taps, 8 experts
    #: top-2 of which 2 are held (experts 2 and 3), vocabulary 256, 32 positions;
    #: a leading dense layer, then conv, attention, conv: every kind of layer
    "lfm2_moe": {
        "model_type": "lfm2_moe", "hidden_size": 64, "intermediate_size": 160,
        "moe_intermediate_size": 48, "num_attention_heads": 4,
        "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
        "layer_types": ["conv", "full_attention", "conv", "conv"],
        "num_hidden_layers": 4, "num_dense_layers": 1, "num_experts": 2,
        "num_experts_per_tok": 2, "routed_scaling_factor": 1,
        "norm_topk_prob": True, "use_expert_bias": True, "norm_eps": 1e-5,
        "rope_theta": 1000000, "vocab_size": 256, "seq_len": 32,
        "share": {"chips_sharing_a_layer": 4, "num_experts": 8,
                  "experts_held": [2, 2], "vocab_rows": [0, 256]}},
    #: hidden 64, 4 heads of 16, 4 taps, 16 experts in 4 groups of which the 2
    #: best are kept, top 2, 2 held (experts 4 and 5: half of group 1), one shared
    #: expert, vocabulary 256, 32 positions; published layers 3 to 6 of a period
    #: of 6: delta rule, delta rule, latent attention, delta rule, the first with
    #: a dense MLP
    "ling3_flash": {
        "model_type": "ling3_flash", "hidden_size": 64, "intermediate_size": 160,
        "moe_intermediate_size": 48, "moe_shared_expert_intermediate_size": 48,
        "num_attention_heads": 4, "head_dim": 16, "q_lora_rank": None,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "rope_theta": 6000000, "rms_norm_eps": 1e-6,
        "short_conv_kernel_size": 4, "kda_lower_bound": -5, "kda_safe_gate": True,
        "no_kda_lora": True, "linear_silu": True, "group_norm_size": 1,
        "gated_attention_proj_granularity_type": "head_wise",
        "score_function": "sigmoid", "layer_group_size": 6,
        "num_hidden_layers": 4, "first_k_dense_replace": 1, "num_experts": 2,
        "num_experts_per_tok": 2, "n_group": 4, "topk_group": 2,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "expert_swiglu_limit_list": [0, 0, 0, 0, 0, 0, 0, 4],
        "share_expert_swiglu_limit_list": [0, 0, 0, 0, 0, 0, 0, 5],
        "vocab_size": 256, "seq_len": 32,
        "share": {"chips_sharing_a_layer": 8, "num_experts": 16,
                  "experts_held": [4, 2], "vocab_rows": [0, 256], "first_layer": 3}},
    #: hidden 64, 2 heads of 32, windows of 8 positions in chunks of 2, rows of
    #: 32 (four windows: the last reads twelve summaries), 2 layers, 3 heads
    "evabyte": {
        "model_type": "evabyte", "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 2, "num_key_value_heads": 2, "window_size": 8,
        "chunk_size": 2, "num_pred_heads": 3, "vocab_size": 32,
        "num_hidden_layers": 2, "rope_theta": 100000, "rms_norm_eps": 1e-5,
        "norm_add_unit_offset": True, "fp32_skip_add": True, "fp32_logits": True,
        "init_std": 0.05, "attention_class": "eva", "hidden_act": "silu",
        "attention_bias": False, "tie_word_embeddings": False, "rope_scaling": None},
    #: hidden 64; Mamba-2 with 8 heads of 16 in 4 groups of state 16 (expand 2),
    #: 4 heads and 2 groups held (heads 4-7, groups 2-3), chunks of 16: 32
    #: positions are two; attention with 4 query and 2 key/value heads of 16, 2
    #: and 1 held (the second pair); 16 experts of width 48 in a latent of 32,
    #: the 6 best a token, 2 held (experts 4 and 5: fewer held than chosen), a
    #: shared expert of 96 columns of which 24 are held; vocabulary 256;
    #: MEM*E and an MTP module *E
    "nemotron_h": {
        "model_type": "nemotron_h", "hidden_size": 64, "expand": 2,
        "mamba_num_heads": 4, "mamba_head_dim": 16, "n_groups": 2,
        "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
        "use_conv_bias": True, "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
        "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 16,
        "n_routed_experts": 2, "num_experts_per_tok": 6, "moe_intermediate_size": 48,
        "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 96,
        "n_shared_experts": 1, "routed_scaling_factor": 5, "norm_topk_prob": True,
        "n_group": 1, "topk_group": 1, "layer_norm_epsilon": 1e-5,
        "num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
        "num_nextn_predict_layers": 1, "mtp_hybrid_override_pattern": "*E",
        "vocab_size": 256, "seq_len": 32,
        "share": {"chips_sharing_a_layer": 8, "tensor_parallel": 2,
                  "n_routed_experts": 16, "mamba_num_heads": 8, "n_groups": 4,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "experts_held": [4, 2], "mamba_heads_held": [4, 4],
                  "mamba_groups_held": [2, 2], "attention_heads_held": [2, 2],
                  "kv_heads_held": [1, 1], "shared_columns": [24, 24],
                  "vocab_rows": [0, 256], "first_layer": 3, "mtp_loss_weight": 0.1}},
    #: hidden 64, 14 query heads of 16 over 2 key/value heads (7 a group), a
    #: window of 8 of the 32 positions, 8 experts of width 48 of which the 2 best
    #: a token and 2 are held (experts 2 and 3), vocabulary 256; a global layer
    #: without a rotary turn, then a sliding layer with one
    "smallthinker": {
        "model_type": "smallthinker", "hidden_size": 64, "head_dim": 16,
        "num_attention_heads": 14, "num_key_value_heads": 2,
        "moe_ffn_hidden_size": 48, "moe_num_primary_experts": 2,
        "moe_num_active_primary_experts": 2,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_hidden_layers": 2, "sliding_window_layout": [0, 1],
        "rope_layout": [0, 1], "sliding_window_size": 8, "rope_theta": 1500000,
        "rope_scaling": None, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "max_position_embeddings": 32, "vocab_size": 256, "seq_len": 32,
        "share": {"chips_sharing_a_layer": 4, "moe_num_primary_experts": 8,
                  "experts_held": [2, 2], "vocab_rows": [0, 256], "first_layer": 0}},
}

#: model -> (the configuration whose reference it reads, the keywords of that
#: reference's `init_params`). Nemotron: 0.16 at a hidden size of 64 gives the
#: projections the size 0.02 gives them at 4,096, so the scan adds what the
#: skip does
_CONFIGS = {
    "glm4_moe_lite": ("glm47-flash-ep8-tau4", {}),
    "lfm2_moe": ("lfm2-8b-a1b-ep4-tau4", {}),
    "ling3_flash": ("ling3-flash-ep64-tau4", {}),
    "evabyte": ("evabyte-l4-tau4", {"std": 0.05}),
    "nemotron_h": ("nemotron3-super-tp4-ep64-tau4", {"std": 0.16}),
    "smallthinker": ("smallthinker-21b-ep4-tau4", {}),
}


# -- inputs and comparisons ---------------------------------------------------

def _x(seed, shape=(ROWS, POS, D)):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _ids(seed, shape=(ROWS, POS), vocab=256):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab, jnp.int32)


def max_err(got, want):
    """The largest difference, relative to the wanted result's own scale."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))) / (float(np.max(np.abs(want))) + 1e-30)


def _close(got, want, policy="float32", tol=None):
    assert max_err(got, want) < (tol or (2e-5 if policy == "float32" else BF16_TOL))


def _per_row(fn, x):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([fn(x[r]) for r in range(x.shape[0])])


def ssd_without_its_state(real):
    """A chunked scan's broken twin: every chunk worked alone, from a zero
    state (`real`: `ops.ssd.ssd`, on either of its forms)."""
    def ssd(x, dt, a, b, c, chunk=CHUNK, **kw):
        cut = lambda t, i: t[:, i:i + chunk]
        return jnp.concatenate([real(cut(x, i), cut(dt, i), a, cut(b, i), cut(c, i), chunk, **kw)
                                for i in range(0, x.shape[1], chunk)], axis=1)
    return ssd


def norm_err(got, want):
    return float(jnp.linalg.norm(got - want)) / (float(jnp.linalg.norm(want)) + 1e-30)


# -- a model's case -----------------------------------------------------------

@functools.cache
def load(rel):
    """The module in the file `rel` of the checkout, run once a process."""
    name = os.path.basename(rel).removesuffix(".py").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, rel))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reference(config):
    """`benchmark/configs/<config>.reference.py`, loaded once a process."""
    return load(f"benchmark/configs/{config}.reference.py")


class Case:
    """One model at its tiny size: `ref` its configuration's reference,
    `layers` and `table` the reference's layer table of the tiny file."""

    def __init__(self, model):
        self.model, self.tiny = model, TINY[model]
        self.config, self.init = _CONFIGS[model]
        self.ref = reference(self.config)
        self.layers = self.ref.layer_table(self.tiny)
        self.table = {name: (kind, a) for name, kind, a in self.layers}
        self.vocab = self.tiny["vocab_size"]

    def params(self, seed):
        """The reference's weight draw `seed`: {layer: {name: array}}, drawn
        once a process (the dicts are the caller's own)."""
        return {layer: dict(lp) for layer, lp in _draw(self, seed).items()}

    def spec(self, rows=ROWS, positions=POS, **over):
        return zoo.SEQUENCE_MODELS[self.model](
            dict(self.tiny, **over), rows=rows, positions=positions)

    def ids(self, seed, shape=(ROWS, POS)):
        return _ids(seed, shape, self.vocab)


case = functools.cache(Case)


@functools.cache
def _draw(c, seed):
    return c.ref.init_params(seed, c.layers, **c.init)


@functools.cache
def compiled(model):
    """The `CompiledNet` of a model's tiny file."""
    return CompiledNet.compile(case(model).spec())


@functools.cache
def program_loss_and_grads(model, policy):
    """(params, ids) -> ((loss, blobs), gradients) of the program under
    `policy`: one jitted function a model and policy, so traced once (the
    policy is read at trace time and is no part of jax's cache key)."""
    loss_fn = compiled(model).loss_fn("loss")
    fn = jax.jit(jax.value_and_grad(
        lambda p, ids: loss_fn(p, {"tokens": ids}, None), has_aux=True))

    def under_policy(params, ids):
        with precision.policy(policy):
            return fn(params, ids)

    return under_policy


@functools.cache
def reference_loss_and_grads(model, **row_loss_kw):
    """(params, ids) -> (loss, gradients) of the reference: the mean of its
    `row_loss` over the rows, float32 at "highest", under one `jax.jit`."""
    c = case(model)

    def row(p, ids):
        out = c.ref.row_loss(p, ids, layers=c.layers, **row_loss_kw)
        return out[0] if isinstance(out, tuple) else out

    @jax.jit
    def fn(params, ids):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(lambda p: sum(
                row(p, ids[r]) for r in range(ids.shape[0])) / ids.shape[0])(params)

    return fn


# -- the three checks ---------------------------------------------------------

@functools.cache
def _program_side(program, policy):
    """A table's program side, jitted once a policy (read at trace time)."""
    return jax.jit(lambda p, x: program(p, x))


@functools.cache
def _reference_side(ref_row):
    """A table's reference side over the rows, "highest", jitted once."""
    return jax.jit(lambda p, x: _per_row(lambda r: ref_row(p, r), x))


def check_layer(table, kind, policy, seeds=(1, 2)):
    """`table[kind]` = (seed -> the layer's parameters, (p, x) -> the
    program's result, (p, row) -> the reference's): the two agree on two
    weight draws, to 2e-5 of the result's scale in float32 and BF16_TOL
    under bfloat16 (the reference stays float32)."""
    draw, program, ref_row = table[kind]
    program, ref_rows = _program_side(program, policy), _reference_side(ref_row)
    for seed in seeds:
        p, x = draw(seed), _x(seed)
        with precision.policy(policy):
            got = program(p, x)
        want = ref_rows(p, x)
        assert float(jnp.max(jnp.abs(want))) > 1e-4
        _close(got, want, policy)


def check_loss_and_every_gradient(model, policy, params, ids, *, loss_tol,
                                  grad_tol, err=norm_err):
    """The program's loss and every stored parameter's gradient under
    `policy` against the reference's: the loss to `loss_tol` (absolute), a
    gradient to `grad_tol` (a number, or parameter name -> number) in `err`;
    a router's bias takes no gradient at all. Returns the program's blobs,
    its gradients and the reference's."""
    (loss, blobs), grads = program_loss_and_grads(model, policy)(params, ids)
    want, want_grads = reference_loss_and_grads(model)(params, ids)
    assert abs(float(loss) - float(want)) <= loss_tol, (float(loss), float(want))
    assert set(grads) == set(want_grads)
    for layer, lp in want_grads.items():
        for name, g in lp.items():
            if name == "router_bias":
                assert float(jnp.max(jnp.abs(grads[layer][name]))) == 0
                continue
            tol = grad_tol(name) if callable(grad_tol) else grad_tol
            e = err(grads[layer][name], g)
            assert e < tol, (layer, name, e)
    return blobs, grads, want_grads


@functools.cache
def reference_round(model, tau, rows=ROWS, draw=None, **round_kw):
    """(weights, ids [tau, rows, positions], the reference's round from
    them), made once a process. `draw(seed)` makes the weights where the
    reference's own draw is not what the model's suite trains from."""
    c = case(model)
    params = (draw or c.params)(8)
    ids = np.asarray(c.ids(78, (tau, rows, POS)))
    want = c.ref.round_reference(params, lambda t, w: ids[t], tau=tau,
                                 solver=SOLVER, layers=c.layers, **round_kw)
    return params, ids, want


def tiny_round(model, tmp_path, tau, rows=ROWS, draw=None, **round_kw):
    """One round's case through the apps' own door: the tiny file written
    out, `RunConfig` -> `resolve_spec` -> (`make_trainer`: `build_trainer`,
    float32, one device, no health pass), beside `reference_round`'s
    weights, ids and wanted round."""
    from sparknet_tpu.apps.train_loop import build_trainer, resolve_spec
    from sparknet_tpu.parallel import make_mesh
    from sparknet_tpu.utils.config import RunConfig

    c = case(model)
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(dict(c.tiny, seq_len=POS)))
    cfg = RunConfig.from_dict({
        "model": str(path), "tau": tau, "local_batch": rows,
        "precision": "float32", "solver": SOLVER, "n_devices": 1,
        "health": {"enabled": False}})
    spec = resolve_spec(cfg)
    assert spec.name == model
    assert spec.inputs == (InputSpec("tokens", (rows, POS), "int32"),)
    params, ids, want = reference_round(model, tau, rows, draw, **round_kw)
    return types.SimpleNamespace(
        path=path, cfg=cfg, spec=spec, params=params, ids=ids, want=want,
        make_trainer=lambda: build_trainer(cfg, spec, make_mesh(1)))


def program_round(model, trainer, params, ids):
    """(the state after, what `correct` reads of it) of one round of the
    program (the token driver's `check_round`, in small)."""
    state, loss = trainer.train_round(trainer.state_from_params(params),
                                      trainer.place_batches({"tokens": ids}),
                                      jax.random.PRNGKey(0))
    norm = lambda x: float(jnp.linalg.norm(x))
    flat = lambda fn: {f"{l}/{n}": fn(l, n) for l, lp in params.items() for n in lp}
    layer, leaf = case(model).ref.PROBE_LEAF
    return state, {
        "loss": float(loss),
        "update_norms": flat(lambda l, n: norm(state.params[l][n][0] - params[l][n])),
        "momentum_norms": [flat(lambda l, n: norm(state.momentum[l][n][0]))],
        "probe": [np.asarray(state.momentum[layer][leaf][0])]}


#: model -> the columns of what its blocks' `InnerProduct`s make a position
#: (`seq_layers.IP_OUT`), a product each: a head's vocabulary (GLM's and
#: Nemotron's second head runs on the first's matrix, `param_from`; LFM2's on
#: the table's, `transposed`; EvaByte's one product makes its three heads'
#: 32 ids each, in float32 whatever the policy), and the projection into
#: Nemotron's MTP module, as wide as the stream
BLOCK_PRODUCTS = {
    "glm4_moe_lite": (256, 256), "lfm2_moe": (256,), "ling3_flash": (256,),
    "evabyte": (3 * 32,), "nemotron_h": (256, 256, D), "smallthinker": (256,)}


def check_products_kept(model, report, tau, rows=ROWS):
    """A round's account of what its blocks' `InnerProduct`s keep
    (`program_report("train_round")["recompute"]`): every product on the
    forward path (off the chip the scan over `tau` steps is unrolled into
    one step body), none made again, and a step's float32 results kept
    whole."""
    columns = BLOCK_PRODUCTS[model]
    assert report["recompute"][IP_OUT] == {
        "maker": IP_OUT, "step_bodies": 1, "forward": tau * len(columns),
        "backward": 0, "kept_bytes": rows * POS * sum(columns) * 4}


def check_routing_kept(report, tau, experts, rows=ROWS):
    """A round's account of what its expert blocks keep of their routing
    (`recompute["moe_route"]`; `experts`: every expert layer's MoEParam, an
    MTP module's with them): a score product and the choice's `top_k`s (the
    CPU's `TopK` custom calls: one, or three where the choice is limited to
    groups) a layer and step on the forward path, none made again, and a
    step's kept bytes -- the chosen ids and raw scores (int32, float32), the
    plan's `tok`, `row_slot` (int32 a buffer row), `row_ok` (a byte), the
    held experts' group sizes and, these tiny layers' sums running as k
    gathers, `slot_row` and `slot_ok` (int32 and a byte a slot)."""
    from sparknet_tpu.model.seq_layers import (MOE_ROUTE, moe_capacity,
                                               sum_walks_buffer)
    tokens = rows * POS
    assert not any(sum_walks_buffer(moe_capacity(p, tokens), tokens,
                                    p.num_experts_per_tok) for p in experts)
    assert report["recompute"][MOE_ROUTE] == {
        "maker": "router", "step_bodies": 1,
        "forward": tau * sum(4 if p.n_group > 1 else 2 for p in experts),
        "backward": 0, "kept_bytes": sum(
            13 * tokens * p.num_experts_per_tok + 9 * moe_capacity(p, tokens)
            + 4 * p.experts_held[1] for p in experts)}


def check_round(got, want, rel):
    """The round's loss to 2e-5 and every stored parameter's change and
    momentum, by their norms, to `rel`."""
    assert got["loss"] == pytest.approx(want["loss"], abs=2e-5)
    for key, norm in want["update_norms"].items():
        assert got["update_norms"][key] == pytest.approx(norm, rel=rel, abs=1e-9), key
        assert got["momentum_norms"][0][key] == pytest.approx(
            want["momentum_norms"][0][key], rel=rel, abs=1e-9), key


# -- GLM's parts that the other models' suites read ---------------------------

MLA_P = MLAttentionParam(num_heads=2, q_lora_rank=24, kv_lora_rank=16,
                         qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                         rope_theta=1e6, eps=1e-5)
MOE_P = MoEParam(n_routed_experts=8, experts_held=(2, 2), num_experts_per_tok=2,
                 intermediate_size=48, n_shared_experts=1,
                 routed_scaling_factor=1.8, norm_topk_prob=True)


def _params(seed, layer="l1_moe", bias_scale=1.0):
    """One layer's weights of GLM's tiny file."""
    p = case("glm4_moe_lite").params(seed)[layer]
    if "router_bias" in p:  # a bias large enough to change who is chosen
        p = dict(p, router_bias=p["router_bias"] * bias_scale)
    return p


def attention_block(mla_p=MLA_P, positions=POS, d=D):
    """(net, params, x, loss) of one recomputation block as a decoder's
    attention half is: norm, latent attention, residual sum."""
    tag = dict(block="b")
    net = CompiledNet.compile(NetSpec(
        name="blk", inputs=(InputSpec("x", (ROWS, positions, d)),), layers=(
            LayerSpec(name="n", type="RMSNorm", bottoms=("x",), tops=("xn",),
                      rmsnorm=RMSNormParam(), **tag),
            LayerSpec(name="a", type="MLAttention", bottoms=("xn",), tops=("y",),
                      mla=mla_p, **tag),
            LayerSpec(name="r", type="Eltwise", bottoms=("x", "y"), tops=("z",),
                      **tag))))
    params = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    loss = lambda p, x: jnp.sum(
        net.apply(p, {"x": x}, train=True)["z"].astype(jnp.float32))
    return net, params, jax.ShapeDtypeStruct((ROWS, positions, d), jnp.float32), loss


def benchmark_expert_layers(config: str):
    """(every expert layer's MoEParam, a step's tokens, the model's width) of
    the benchmark's configuration `config`, built from its own file."""
    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json")) as f:
        c = json.load(f)
    spec = zoo.SEQUENCE_MODELS[c["model_type"]](
        c, rows=c["local_batch"], positions=c["seq_len"])
    return ([l.moe for l in spec.layers if l.type == "MoE"],
            c["local_batch"] * c["seq_len"], c["hidden_size"])
