"""repro — Ginkgo's platform-portability design as a multi-pod JAX framework.

Subpackages:
  core         the paper's contribution: executors, op registry, coop groups
  sparse       COO/CSR/ELL/SELL-P + executor-dispatched SpMV
  solvers      CG/FCG/BiCGSTAB/CGS/GMRES + Jacobi/block-Jacobi/ParILU
  kernels      Pallas TPU kernels (flash attention, spmv, rmsnorm, ssd, rwkv6)
  nn, models   layer library + the 10 assigned architectures
  configs      architecture/shape configuration system
  data, optim, checkpoint, runtime   training substrate
  distributed  sharding rules, collective matmuls
  launch       mesh, dry-run, train/serve drivers, roofline cost model
"""

__version__ = "0.1.0"

# Importing the library registers the Pallas kernel space (Ginkgo: the device
# backends are linked into the library), so a Pallas executor finds every
# kernel whatever the caller happened to import first.
from repro import kernels as _kernels  # noqa: E402,F401
