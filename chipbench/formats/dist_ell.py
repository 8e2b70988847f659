"""dist_ell: the program's ``DistEll`` over ``chips`` contiguous row slabs
of ``local_rows`` rows each, packed by ``DistEll.from_matrix`` from the ELL
conversion of the request's CSR arrays."""

DISTRIBUTED = True


def convert(system, values, config: dict):
    from repro import sparse
    from repro.distributed import DistEll, Partition

    chips, local_rows = int(config["chips"]), int(config["local_rows"])
    if chips * local_rows != system.n:
        raise ValueError(f"{chips} slabs of {local_rows} rows do not hold "
                         f"{system.n} rows")
    A = sparse.ell_from_csr_host(system.indptr, system.indices, values,
                                 (system.n, system.n))
    return DistEll.from_matrix(A, Partition.from_part_sizes([local_rows] * chips))
