"""The recurrent state's share of the bytes the traced decode rounds had to
move: from the program's ``state_row_steps`` (the ``state_rows`` arg of the
traced ``lm/decode_step`` spans: active slots x KDA layers a round), each row
read and written, over the rounds' least bytes
(``costs_solar2.decode_least_bytes``)."""
import jax.numpy as jnp

from benchmarks.harness import costs_solar2


def read(rec: dict):
    least = costs_solar2.traced_decode_least_bytes(
        rec, rec["counters"].get("lm.traced_decode_rounds"))
    if least is None:
        return None
    dtype_bytes = jnp.dtype(rec["config"]["assumed"]["serve_dtype"]).itemsize
    state = (rec["counters"]["lm.traced_state_rows"]
             * costs_solar2.state_row_bytes(rec["config"], dtype_bytes))
    return state / least * 100.0
