"""The arithmetic of the comparisons that decide ``correct``."""
import numpy as np


def leaf_norms(leaves):
    """Euclidean norm of each named host array, in float64."""
    return {k: float(np.linalg.norm(np.asarray(v, np.float32)
                                    .astype(np.float64).ravel()))
            for k, v in leaves.items()}


def leaf_gaps(prog, ref, skip=()):
    """| ||prog|| - ||ref|| | / max(||ref||, median leaf's ||ref||) by leaf: the
    gap between the norms, not the norm of the difference, each leaf against
    its own reference norm or the median leaf's, whichever is larger (some
    gradients are all but zero)."""
    names = [k for k in ref if k not in skip]
    median = float(np.median([ref[k] for k in names]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], median, 1e-300)
            for k in names}


def still_leaves(ref_grad_norms, share=1e-3):
    """Leaves whose reference gradient is nought to rounding (under
    ``share`` of the median leaf's): they move by round-off alone and are
    left out of the parameters' change."""
    median = float(np.median(list(ref_grad_norms.values())))
    return {k for k, v in ref_grad_norms.items() if v < share * median}
