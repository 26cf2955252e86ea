"""% of its roofline the GEMM kernel reached: least time of the GEMMs over the device time of all their launches."""


def read(run):
    return run.kernel_roofline("psum_matmul")
