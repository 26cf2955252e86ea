"""% of its roofline the grouped expert GEMM kernel reached: least time of the grouped GEMMs (logical rows, every expert's weights once) over the device time of all their launches."""


def read(run):
    return run.kernel_roofline("psum_grouped_matmul")
