"""ell: the program's ELL format on one chip, converted on the host by
``sparse.ell_from_csr_host`` from the request's CSR arrays."""

DISTRIBUTED = False


def convert(system, values, config: dict):
    from repro import sparse

    return sparse.ell_from_csr_host(system.indptr, system.indices, values,
                                    (system.n, system.n))
