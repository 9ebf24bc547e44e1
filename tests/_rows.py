"""Serving a model with SSM layers with each step's rows out of order."""
import jax.numpy as jnp
import numpy as np

from repro.serving.engine import DecodeJob, PrefillJob


def _reversed(job):
    """``job`` with its rows in reverse order."""
    if isinstance(job, DecodeJob):
        return DecodeJob(job.slots[::-1], job.reqs[::-1], job.seq_ids[::-1],
                         job.last_tok[::-1].copy())
    return PrefillJob(job.slots[::-1], job.reqs[::-1], job.seq_ids[::-1],
                      job.toks[::-1].copy(), job.offs[::-1].copy(),
                      job.clens[::-1].copy())


def serve_rows_out_of_order(eng, reqs):
    """Serve ``reqs`` (one per slot) through ``eng`` with every chunk
    and decode step's rows reversed, over a state filled with noise.
    After each step the slots it did not name keep their SSM state and
    conv tail bit for bit.  Returns the rows each step ran."""
    rng = np.random.default_rng(7)
    eng.ssm_state = jnp.asarray(rng.normal(size=eng.ssm_state.shape),
                                eng.ssm_state.dtype)
    eng.conv_tail = jnp.asarray(rng.normal(size=eng.conv_tail.shape),
                                eng.conv_tail.dtype)
    rows = []
    if eng.chunk_tokens:
        eng.admit_chunked(reqs)
    else:
        eng.prefill(reqs)
    while eng.has_prefill_work() or eng.has_decode_work():
        before = np.asarray(eng.ssm_state), np.asarray(eng.conv_tail)
        if eng.has_prefill_work():
            job = _reversed(eng.export_prefill_job())
            eng.run_chunk_job(job)
        else:
            job = _reversed(eng.export_decode_job())
            eng.decode(job)
        rows.append(len(job))
        kept = [s for s in range(eng.max_slots) if s not in job.slots]
        np.testing.assert_array_equal(np.asarray(eng.ssm_state)[:, kept],
                                      before[0][:, kept])
        np.testing.assert_array_equal(np.asarray(eng.conv_tail)[:, kept],
                                      before[1][:, kept])
    return rows
