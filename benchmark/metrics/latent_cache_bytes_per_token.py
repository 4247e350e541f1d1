"""Bytes the latent cache groups' arenas hold over the tokens they can
hold, all layers, as the engine's registry says
(``serving/kv_latent_bytes_per_token``)."""


def read(view):
    return view["observed"].get("kv_latent_bytes_per_token")
