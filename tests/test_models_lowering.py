"""Every registered serving family's programs lower to the text they lowered
to: the instrument for a change that moves or shares model code.

A case is the sha1 of ``jax.jit(f).lower(<shapes>).as_text()`` at the family's
tiny config.  The StableHLO text holds no file name, line or Python function
name, so moving a function between modules leaves it alone, and a changed
body, default or argument does not.  The file imports ``ray_tpu.models`` (the
package) alone, so it runs on any tree that registers the families.

History.  The hashes were read on PR 63's tree (``c9b289f``), before PR 64
moved the shared layers out of the families' files.  The MiMo-V2, Mistral-4
and LongCat prefill / decode hashes are older: they came here from
``test_laguna.py`` and ``test_mistral4.py`` as they stood, first read on
PR 45's tree; PR 53 re-read all six (the expert layers count their loop's
chunks, the sigmoid router picks its scores by a select) and PR 54 the three
decode steps and LongCat's prefill (``held_experts``' one-chunk form).
PR 66 re-read ``("llama", "prefill")`` alone: ``llama_prefill`` scores
through ``layers.blocked_attention`` (its key-value heads unrepeated, no
``[S, S]`` scores or mask); every other hash stayed, so no other family's
program moved.  PR 67 added Kimi-Linear's three, read on its own tree, and
re-read none: the delta rule's chunked form and ``pack_state`` moved from
``olmo_hybrid.py`` to ``delta_rule.py`` and ``mla_blocked`` from
``mistral4.py`` to ``mla.py`` with their bodies as they were, and the
vector gate, the one-matrix query and ``rotate=False`` are branches that a
family which does not ask for them never traces.  A PR that re-reads a hash
says here which and why.
"""
import hashlib

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import (GPT2Config, GraniteHConfig, KimiLinearConfig,
                            LagunaConfig, LlamaConfig, LongcatConfig,
                            MimoV2Config, MinicpmSalaConfig, Mistral4Config,
                            NemotronHConfig, OlmoHybridConfig, model_family)

CONFIGS = {
    "gpt2": GPT2Config, "llama": LlamaConfig, "longcat": LongcatConfig,
    "nemotron_h": NemotronHConfig, "mimo_v2": MimoV2Config,
    "mistral4": Mistral4Config, "laguna": LagunaConfig,
    "olmo_hybrid": OlmoHybridConfig, "granite_h": GraniteHConfig,
    "minicpm_sala": MinicpmSalaConfig, "kimi_linear": KimiLinearConfig,
}

HASHES = {
    ("gpt2", "prefill"): "80ff298dded36aabcfc54d915eacf1adffd037d6",
    ("gpt2", "decode_step"): "2e685fbff3f022dc498ea63415bfbdaab17f26a4",
    ("gpt2", "loss"): "8c5315f0e3be6c89eb7662c1c2b9b4b65d35d117",
    ("gpt2", "loss_grad"): "46842b81dc9cd3e20c0ba8b183125f276b1a7806",
    ("llama", "prefill"): "5c8b025a0e0fe7ae06bf54802e26e0c771f22fda",
    ("llama", "decode_step"): "a69a320eee2427cc366939752d1a78b626d8cdb1",
    ("llama", "loss"): "6259211fe07b8e9fd911bea322ccdf0f474d47bf",
    ("longcat", "prefill"): "d5bc64ad9a4e93b9edd4ab6d292cdea55242bb76",
    ("longcat", "decode_step"): "a67b2c217adce5c1a2c80f801316a2c602e10733",
    ("longcat", "loss"): "cb6b69dd73a110b55e5b6521cb07fcb39b859382",
    ("nemotron_h", "prefill"): "f03346bcf7f41a4c766a65995daaf0ab815908c4",
    ("nemotron_h", "decode_step"): "4d2dfb8ab60e972900b37a9323ee5495c5d6902c",
    ("nemotron_h", "loss"): "c1cf5f2cbcd342ab576db367b9fada20f2198519",
    ("mimo_v2", "prefill"): "16772087bf73326081b75dc93bfb0dfb9c4749de",
    ("mimo_v2", "decode_step"): "8bd0dfba3e02f082be71984ad01897d310c8c355",
    ("mimo_v2", "loss"): "b65c4da336997869a779f87af9b0fec029f1181a",
    ("mistral4", "prefill"): "d979f58cccbca1cb44fa5733c4f251147805c04b",
    ("mistral4", "decode_step"): "7c629245dec20c29d937ccafeb35b4df04b77eeb",
    ("mistral4", "loss"): "894edf81d6ed380dbe5e2a6bbfb6c66a2ad0e0df",
    ("laguna", "prefill"): "092d08b694c5183681f06e3bf4567198005f99ee",
    ("laguna", "decode_step"): "ef7a5f849053b25470ac83c1b2c5ac8c5da5d1b2",
    ("laguna", "loss"): "1e0161d9f1cfe0d88930a7dbba6a21db4cdc2221",
    ("olmo_hybrid", "prefill"): "03c47bca3a2a4ec2b8b82d552439594738353c60",
    ("olmo_hybrid", "decode_step"): "41940fa98a3f365c48633875aec0fc073929e23c",
    ("olmo_hybrid", "loss"): "c982d2330cdc800892777336f202e756dce133b0",
    ("granite_h", "prefill"): "c6c9ce86ef6504f59e2d43694895a2c8689ca0c1",
    ("granite_h", "decode_step"): "f82ffb32868de5bbe289fcfcde9a8ccde35fc427",
    ("granite_h", "loss"): "215ef16b1b676b4d540b31b64d22aafc284134d9",
    ("minicpm_sala", "prefill"): "311672df83d7f7253a025cf5c50c6b7aeafb912c",
    ("minicpm_sala", "decode_step"): "9384f2d75965c632b36d4426d417df3d2853ca84",
    ("minicpm_sala", "loss"): "165cfb5a34cfda92531400c3e69bba2980a93239",
    ("kimi_linear", "prefill"): "5ea9e0dbe48031f3b45a20fbd3742a979f8691a5",
    ("kimi_linear", "decode_step"): "f5633124a1d2f9d103881a184c0840a73da493fc",
    ("kimi_linear", "loss"): "978105a754526aa582f4d8f1f1d810642175665f",
}


def _ints(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def lowered_text(family: str, program: str) -> str:
    """The StableHLO text of one program of ``family`` at its tiny config:
    ``prefill`` of one row of 64 and ``decode_step`` of four slots of 1024
    (the counted twins where the family has them, as the engine runs), the
    ``loss`` of a ``[2, 32]`` batch, or ``loss_grad``, its gradient."""
    cfg = CONFIGS[family].tiny()
    fam = model_family(cfg)
    params = jax.eval_shape(lambda: fam.init(jax.random.PRNGKey(0), cfg))
    if program == "prefill":
        prefill = fam.prefill_counted or fam.prefill
        one = jax.eval_shape(lambda: fam.init_cache(cfg, 1, 64))
        return jax.jit(lambda p, t, n, c: prefill(p, t, n, c, cfg)).lower(
            params, _ints(1, 64), _ints(1), one).as_text()
    if program == "decode_step":
        step = fam.decode_step_counted or fam.decode_step
        cache = jax.eval_shape(lambda: fam.init_cache(cfg, 4, 1024))
        return jax.jit(lambda p, t, pos, c: step(p, t, pos, c, cfg)).lower(
            params, _ints(4), _ints(4), cache).as_text()
    loss = lambda p, t: fam.loss(p, t, cfg)  # noqa: E731
    if program == "loss_grad":
        loss = jax.grad(loss)
    return jax.jit(loss).lower(params, _ints(2, 32)).as_text()


@pytest.mark.parametrize("family,program", sorted(HASHES),
                         ids=lambda value: value)
def test_the_program_lowers_to_the_text_it_lowered_to(family, program):
    """Reverse mode is asked of GPT-2 alone: the training cells differentiate
    its loss, and six families' blocked attention loops over traced bounds,
    which reverse-mode differentiation refuses."""
    text = lowered_text(family, program)
    assert hashlib.sha1(text.encode()).hexdigest() == HASHES[family, program]
