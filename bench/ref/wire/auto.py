"""The level codecs (``auto`` is nnc-cabac, the paper's DeepCABAC wire).

They carry the integer quantization levels losslessly, so the server
receives exactly the clients' dequantized updates."""


def decode(recon_params, recon_scales):
    return recon_params, recon_scales
