"""The whole step's share of the chip's peak: the FLOPs the window's work
needs, from the configuration and the request log, over the window's
seconds times the chips' peak."""


def read(ctx, work):
    flops = ctx['flops']
    if work == 'serve':
        need = flops.serve_flops(ctx['cfg'], ctx['deliveries'])
    elif work == 'train':
        need = ctx['train_steps'] * flops.train_flops(
            ctx['cfg'], ctx['batch'], ctx['seq'])
    else:
        raise ValueError(f'unknown work {work!r}')
    if not need:
        return None
    return 100.0 * need / (ctx['seconds'] * ctx['chips']
                           * ctx['peak'].flops_bf16)
