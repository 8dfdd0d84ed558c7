"""Device milliseconds per execution of the VAE decode program
(``jit_vae_decode``: one drained image's latents to pixels, batch 1) in
the traced window."""

PROGRAM = 'jit_vae_decode'


def read(run):
    tr = run['trace']
    if tr is None or not tr['module_count'].get(PROGRAM):
        return None
    return tr['per_module_ns'][PROGRAM] / tr['module_count'][PROGRAM] / 1e6
