"""admit_wall_ms_per_request (ms): mean over the fresh requests whose slot was installed between the window's open and the drain's end of: first token sampled minus scheduler pop (match, block allocation, every chunk, the first-token read), on the engine's clock (ServeMetrics admit_wall_s / admissions, both taken at the install)."""

from chipbench.metrics import _phases as P


def read(obs):
    return P.per(obs, 1e3, 'admit_wall_s', 'admissions')
