"""step_temp_gib (GiB): the compiled step program's temporary buffers,
``memory_analysis().temp_size_in_bytes``, per chip."""


def read(ctx):
    return ctx.temp_bytes / 2.0 ** 30
