"""Device time a step, mean over the chips, of the operations under the
``diffusion_noise`` scope: the step's draw, the noised window beside the
clean one and the rows' weights."""

from ddbench import sdar_scopes


def read(ctx):
    return sdar_scopes.diffusion_noise_ms(ctx)
