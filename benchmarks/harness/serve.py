"""Starts the system under test: the engine on the cell's mesh behind
``ApiServer(engine, port=0)`` in this process (one process holds the chip),
with the server settings the cell's file gives."""

from __future__ import annotations

import os


def start(family, params, policy, cell: dict):
    """(server, base url). ``cell["server_env"]`` holds the deployment's
    settings as the environment variables an operator would set
    (SDTPU_BATCH_LADDER, SDTPU_BUCKET_LADDER, SDTPU_COALESCE_WINDOW, ...);
    the dispatcher reads them when the server is built."""
    from stable_diffusion_webui_distributed_tpu.pipeline.engine import Engine
    from stable_diffusion_webui_distributed_tpu.server.api import ApiServer

    for key, value in cell.get("server_env", {}).items():
        os.environ[key] = str(value)
    mesh = None
    if cell.get("mesh"):
        from stable_diffusion_webui_distributed_tpu.runtime.mesh import (
            build_mesh,
        )

        mesh = build_mesh(cell["mesh"])
    engine = Engine(family, params, policy=policy, mesh=mesh,
                    model_name=f"{family.name}-bench")
    server = ApiServer(engine, port=0).start()
    return server, f"http://127.0.0.1:{server.port}"
