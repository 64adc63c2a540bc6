"""Launch layer: the sharded Gram job."""
import numpy as np


def test_gram_job_symmetric_and_correct():
    from repro.launch.gram import run
    G = run(n=8, t=16, kind="dtw")
    assert G.shape[0] >= 8
    sub = G[:8, :8]
    np.testing.assert_allclose(sub, sub.T, rtol=1e-4)
    assert np.allclose(np.diag(sub), 0, atol=1e-4)
