"""Share of its roofline that the grouped matmul over the held experts
(``kernels/moe_gmm``, the megablox ``gmm`` kernel) reaches."""

from bench.roofline import nbytes, share


def gmm_ops_bytes(m: int, k: int, n: int, held: int, experts: int,
                  elem_bytes: int = 2) -> tuple[float, float]:
    """Operations and bytes of one grouped matmul call.

    ``m`` rows in (every pick of every token), of which r = m x held /
    experts fall to the ``held`` experts at balanced routing; each is one
    (k) x (k, n) product, 2 r k n operations.  Bytes: the held experts'
    weights read once, r rows of k read and r rows of n written, each
    element ``elem_bytes``."""
    r = m * held / experts
    return float(2 * r * k * n), float((held * k * n + r * k + r * n) * elem_bytes)


def _match(name, outs, ins):
    """The ``gmm`` custom call, whose last two operands are the sorted rows
    (m, k) and the held experts' weights (held, k, n), and whose output is
    (m, n)."""
    if name != "gmm" or len(outs) != 1 or len(ins) < 2:
        return False
    (_, lhs), (_, rhs) = ins[-2:]
    return (len(lhs) == 2 and len(rhs) == 3 and lhs[1] == rhs[1]
            and outs[0][1] == (lhs[0], rhs[2]))


def _cost(outs, ins, run):
    (dt, (m, k)), (_, (held, _, n)) = ins[-2:]
    return gmm_ops_bytes(m, k, n, held, run.cfg["n_router_experts"], nbytes((dt, (1,))))


def read(run):
    return share(run, _match, _cost)
