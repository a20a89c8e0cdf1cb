"""``Served``: the serving engine under teacher forcing, for the agreement
tests that hold a served model's LOGITS to its plain reference
(tests/test_solar2.py, tests/test_ling3.py)."""
import numpy as np

import jax
import jax.numpy as jnp


class Served:
    """Serve requests teacher-forced (0-based ``forced`` ids a request) and
    keep every logits row the engine picks a token from, by request.  The
    first token's row reaches the host's ``_pick`` (admissions are first in,
    first out); a decode round picks on the device, so the engine's decode
    executable is stood in for by the same step handing out its logits, and
    the forced tokens as the slots' ids (as tests/test_laguna.py does for one
    request; here any number share the rounds).  A request is known by its
    first prompt token."""

    def __init__(self, monkeypatch, engine):
        from bigdl_tpu.models.transformer import generate as G
        from bigdl_tpu.serving import lm_engine
        from bigdl_tpu.serving.kvcache.blocks import SCRATCH_BLOCK
        self.engine, self.rows, self.queue, self.order = engine, {}, {}, []
        self.rounds = []        # the active slots of every decode round
        n = len(engine._arenas())
        step = jax.jit(
            lambda p, token, pos, live, *kv: G._decode_step_paged(
                engine.model, p, token, pos, live, *kv,
                table_width=engine.table_width, attn_impl=engine.decode_attn),
            donate_argnums=tuple(range(4, 4 + n)))

        def pick(logits_row, temperature, key, clamp):
            who = self.order.pop(0)
            self.rows[who].append(np.array(logits_row))
            return int(self.queue[who].pop(0))

        def decode(params, operands, prev_ids, *kv):
            token, pos, _, _, live = lm_engine.split_decode_operands(
                jnp.asarray(operands), engine.slots)
            token = jnp.where(token < 0, prev_ids, token)   # lm_engine.TAKE_PREV
            logits, *rest = step(params, token, pos, live, *kv)
            ids = np.zeros((engine.slots,), np.int32)
            # the round's slots are those its live list names (a slot whose
            # count ended with the round before is seated, and not in it)
            block, owner, _ = np.asarray(live)
            active = sorted(set(owner[block != SCRATCH_BLOCK].tolist()))
            self.rounds.append(active)
            for i in active:
                who = int(engine._slots[i].stream.prompt[0])
                self.rows[who].append(np.array(logits[i]))
                ids[i] = self.queue[who].pop(0)
            return (jnp.asarray(ids), *rest)

        monkeypatch.setattr(lm_engine.LMServingEngine, "_pick", staticmethod(pick))
        monkeypatch.setattr(engine, "_decode_exec", decode)

    def submit(self, prompt, forced):
        who = int(prompt[0]) + 1
        assert who not in self.rows, "requests are told apart by their first token"
        self.rows[who], self.queue[who] = [], list(forced)
        self.order.append(who)
        return who, self.engine.submit(prompt + 1, max_new_tokens=len(forced))

    def logits(self, who):
        return np.stack(self.rows[who])
