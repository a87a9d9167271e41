"""The phase of a step is in every ``op_name``: forward, the forward a
``jax.checkpoint`` runs again (JAX's ``rematted_computation``), backward,
optimizer.  ``benchmark/step_table.py`` reads it there, and the scopes
``rope``, ``heads_layout`` and ``rms_norm`` (``models/decoder.py``, GPT-2's
and ``SelfMultiheadAttn``'s head split) under the blocks.  A tiny window
(K = 2, ``full_block``) of each family is LOWERED here — nothing compiles,
nothing runs — and every ``op_name`` of the lowered text classified, so that
a JAX upgrade that renames its marker fails a test instead of zeroing a
metric."""
import functools
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import step_table  # noqa: E402

#: family -> (config, model, rotates): the seven on ``DecoderLM``
DECODERS = {
    "afmoe": ("AfmoeConfig", "AfmoeLM", True),
    "deepseek_v3": ("DeepseekV3Config", "DeepseekV3LM", True),
    "qwen3_next": ("Qwen3NextConfig", "Qwen3NextLM", True),
    "smallthinker": ("SmallThinkerConfig", "SmallThinkerLM", True),
    "lfm2": ("Lfm2Config", "Lfm2LM", True),
    # no positions in the attention layer / the latent layer's rotation off
    "granite_hybrid": ("GraniteHybridConfig", "GraniteHybridLM", False),
    "kimi_linear": ("KimiLinearConfig", "KimiLinearLM", False),
}
RUN_PHASES = ("forward", "recompute", "backward")
#: the scopes ISSUE 48 opens inside the blocks, by the family that shows them
NEW_SCOPES = {
    "gpt": ("heads_layout", "qkv_split", "attention", "norm_cast"),
    "bert": ("heads_layout", "qkv_split", "norm_cast"),
    "afmoe": ("rope", "heads_layout", "rms_norm", "qkv_split", "output_gate"),
    "qwen3_next": ("qkv_split", "output_gate"),
}


#: the two dense models, on blocks of their own
DENSE = {"gpt": ("GPTConfig", "GPTLM"), "bert": ("BertConfig", "BertForMLM")}


def _model(family: str, remat_policy: str):
    mod = importlib.import_module(f"apex_tpu.models.{family}")
    cfg_cls, model_cls = (getattr(mod, n)
                          for n in (DENSE.get(family) or DECODERS[family])[:2])
    return model_cls(cfg_cls.tiny(remat_policy=remat_policy))


@functools.lru_cache(maxsize=None)
def classified(family: str, remat_policy: str = "full_block"):
    """``[(op_name, scope path, phase, mixed)]`` over the lowered text of the
    family's tiny train window: AMP O2, ``fused_adam``, two steps a dispatch
    through ``FusedTrainDriver``, as the benchmark's runner builds it."""
    import apex_tpu.amp as amp
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.train import FusedTrainDriver

    model = _model(family, remat_policy)
    amp_ = amp.initialize("O2")
    opt = amp.AmpOptimizer(fused_adam(1e-3), amp_)

    def step(carry, ids):
        params, state = carry

        def scaled(mp):
            _, loss = model.apply(
                {"params": opt.model_params(mp)}, ids, labels=ids,
                deterministic=False, rngs={"dropout": jax.random.PRNGKey(0)})
            return amp_.scale_loss(loss, state.scaler[0])

        params, state, _ = opt.step(jax.grad(scaled)(params), state, params)
        return (params, state), {}

    k, seq = 2, 64
    ids = jnp.zeros((1, seq), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
    carry = jax.eval_shape(lambda p: (p, opt.init(p)), params)
    text = FusedTrainDriver(step, steps_per_dispatch=k).lower(
        carry, jax.ShapeDtypeStruct((k, 1, seq), jnp.int32)
    ).as_text(debug_info=True)
    # a named location with a call site behind it is an ``op_name`` or a
    # Python frame; a frame's name holds no ``/``
    names = {n for n in re.findall(r'loc\("([^"]+)"\(#loc', text) if "/" in n}
    return [(n, *step_table.classify(n)) for n in sorted(names)]


def under_blocks(family, scope, kind=None):
    """The phases in which ``scope`` lies under ``layer_*`` (in an
    ``op_name`` whose primitive is ``kind``, where one is given)."""
    return {phase for name, path, phase, _ in classified(family)
            if "layer_*" in path and scope in path[path.index("layer_*"):]
            and (kind is None or name.endswith("/" + kind))}


@pytest.mark.parametrize("family", ["gpt", "afmoe"])
def test_every_op_name_has_one_phase_and_none_is_mixed(family):
    rows = classified(family)
    assert len(rows) > 500
    assert {phase for _, _, phase, _ in rows} == set(step_table.PHASES)
    assert not any(mixed for *_, mixed in rows)
    # the optimizer's own scopes, whatever wraps them
    for name, path, phase, _ in rows:
        if {"apex_amp_step", "apex_amp_cast"} & set(path):
            assert phase == "optimizer", name


@pytest.mark.parametrize("family", ["gpt", "afmoe"])
def test_each_phase_of_the_model_holds_a_product_under_the_blocks(family):
    products = {phase for name, path, phase, _ in classified(family)
                if "layer_*" in path and name.endswith("/dot_general")}
    assert products == set(RUN_PHASES)


@pytest.mark.parametrize("family", ["gpt", "afmoe"])
def test_the_recomputed_forward_is_a_phase_and_no_scope(family):
    rows = classified(family)
    marked = [r for r in rows if step_table.REMAT in r[0].split("/")]
    assert marked and all(phase in ("recompute", "optimizer")
                          for _, _, phase, _ in marked)
    assert not any(step_table.REMAT in path for _, path, _, _ in rows)


def test_a_block_under_no_checkpoint_recomputes_nothing():
    """``gpt2-small.train`` and ``bert-large.train`` run ``remat_policy:
    none``: ``model.recompute_ms_per_step`` has nothing to read there."""
    phases = {phase for _, _, phase, _ in classified("gpt", "none")}
    assert phases == set(step_table.PHASES) - {"recompute"}


@pytest.mark.parametrize("family", sorted(DECODERS))
def test_decoder_scopes_lie_under_the_blocks_in_all_three_phases(family):
    for scope in ("heads_layout", "rms_norm"):
        assert under_blocks(family, scope) == set(RUN_PHASES), scope
    rotates = DECODERS[family][2]
    assert under_blocks(family, "rope") == (set(RUN_PHASES) if rotates
                                            else set())
    # a flax name stays the outer scope: ``<norm's name>/rms_norm``
    assert any(path[-3::2] == ("layer_*", "rms_norm")
               and path[-2].endswith("_norm")
               for _, path, _, _ in classified(family))


@pytest.mark.parametrize("family", ["gpt", "bert"])
def test_the_dense_models_head_split_and_merge_are_heads_layout(family):
    assert under_blocks(family, "heads_layout", "transpose") == set(RUN_PHASES)
    # GPT-2's cache transposes (``_decode`` / ``prefill``) are not this scope:
    # the training path alone opens it
    assert not any("heads_layout" in path for _, path, phase, _
                   in classified(family) if "layer_*" not in path)


@pytest.mark.parametrize("family", sorted(NEW_SCOPES))
def test_a_blocks_own_work_bears_a_name_no_older_reader_matches(family):
    for scope in NEW_SCOPES[family]:
        assert {"forward", "backward"} <= under_blocks(family, scope), scope
        # the benchmark's older readers match whole names or ``apex_``: none
        # of their families' prefixes may sit in a new name
        assert not re.search(
            "apex_|attn_|moe_|gdn_|kda_|ssm_|conv_|mla_|lm_|layer_", scope)
