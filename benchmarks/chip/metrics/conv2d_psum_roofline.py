"""% of its roofline the conv kernel reached: least time for its calls over their device time."""


def read(run):
    return run.kernel_roofline("conv2d_psum")
