"""prefill_ms_per_ktok.st_longdoc (ms): device time of the chunk-prefill programs in the traced stretch per 1,000 prompt tokens prefilled in it."""

from chipbench.metrics import _lib as L


def read(obs):
    sec = L.program_seconds(obs, 'jit__chunk_paged')
    toks = sum((obs.get('work') or {}).get('prefills') or [])
    if not sec or not toks:
        return None
    return 1e3 * sec / (toks / 1000.0)
