"""Draft-verify speculative decoding for the LM slot engine.

A cheap drafter proposes tokens a slot; ONE fixed-shape donated verify
executable scores the candidate positions against the paged target cache;
the accepted prefix is what the offline sampling key chain would have
emitted, so greedy AND sampled speculative streams stay bit-exact vs
offline ``generate()`` (``"replay"``).  See the module docstrings of
:mod:`.draft`, :mod:`.verify`, :mod:`.metrics`.

THREE DRAFTERS, and which models each can serve:

- :class:`DraftModel` -- a SEPARATE model (the target's int8
  ``quantize()`` clone by default, or ``SpecConfig(draft=...)``) with a
  dense ``(L, S, H, cache_len + 1, D)`` arena and prefill programs of its
  own, through the offline slot-cache step: the drafter model is a uniform
  block (GPT-2's; a planned model cannot be one), the TARGET any model
  with a ``(k, v)`` or a latent pool and no recurrent layers.  k drafts a
  round, chain or tree verify, replay or rejection sampling.
- :class:`NgramDrafter` (``drafter_compute="ngram"``) -- NO model:
  proposals are suffix matches in the request's own prompt + emitted
  tokens; no device program, no arena.  Any target the verify step
  serves: ``(k, v)`` pools (chain or tree) and latent pools (chain).
- :class:`SelfDrafter` -- the target's OWN PREDICTION MODULE
  (``TransformerLM(mtp=...)``, DeepSeek-V3's multi-token prediction),
  THE ONE THAT SHARES THE TARGET'S POOL: its block's latent rows are one
  more arena layer of the target's ``BlockPool(latent=True)``, filled by
  the target's prefills and shared by the radix cache with the prefix;
  no dense arena, no second prefill program, no second model.  A round
  is one program (``generate._selfdraft_step_paged``: verify at W = 2,
  pick, the module's pairs) that hands the host ``(S, 4)`` ids and
  counts, never logits; a slot advances by 1 or 2, and the next round
  is enqueued before this one's counts are read (greedy slots: the step
  takes their tokens and positions from this round's output on the
  device).  A model of latent
  layers alone with a prediction module; ``SpecConfig(k=1)`` (or
  ``spec=1``) on such a model selects it -- the model's own attribute,
  no switch; tree verify, rejection sampling and k > 1 are refused at
  construction (``lm_engine._REFUSALS``).

A model with recurrent layers is refused speculation whoever drafts (a
rejected draft would need the state rolled back).

Speculation 2.0 widens the chain to a small candidate TREE
(``SpecConfig(tree=True)``): the drafter's spine plus ranked
runner-up alternates are scored in one pass per pre-lowered
:class:`TreeShape`, per-slot depth/width adapts over the shape ladder
from the acceptance EMA.

Enable with ``LMServingEngine(model, spec=SpecConfig(k=4))``.
"""
from bigdl_tpu.serving.spec.draft import (DraftModel, NgramDrafter,
                                          SelfDrafter)
from bigdl_tpu.serving.spec.metrics import SpecMetrics
from bigdl_tpu.serving.spec.verify import (SpecConfig, TreeShape,
                                           accept_row, accept_walk,
                                           default_tree_shapes, draft_pick,
                                           pick_token, tree_accept_walk)

__all__ = ["DraftModel", "NgramDrafter", "SelfDrafter", "SpecConfig",
           "SpecMetrics",
           "TreeShape", "accept_row", "accept_walk", "default_tree_shapes",
           "draft_pick", "pick_token", "tree_accept_walk"]
