"""JAX model-serving component: versioned model server + traffic-split Service.

Replaces TF-Serving / TensorRT Inference Server behind the same surface:
gRPC :9000 + REST :8500 ports and per-version Deployments with a
weight-split Service (reference: ``/root/reference/kubeflow/tf-serving/
tf-serving-template.libsonnet:33-48``, version split
``tf-serving-service-template.libsonnet`` / ``prototypes/
tf-serving-service.jsonnet:8``, prometheus config ``:128-130``).
"""

from __future__ import annotations

from typing import Any, Dict, List

from kubeflow_tpu.config.deployment import DeploymentConfig
from kubeflow_tpu.k8s import objects as o
from kubeflow_tpu.manifests.registry import register

DEFAULTS: Dict[str, Any] = {
    "name": "model-server",
    "image": "kubeflow-tpu/serving:v1alpha1",
    "model_base_path": "/models/default",
    "version": "v1",
    "replicas": 1,
    "rest_port": 8500,
    "grpc_port": 9000,
    "tpu_chips": 0,  # 0 = CPU serving; >0 requests google.com/tpu
    "batch_timeout_ms": 5,
    "max_batch_size": 8,
    # continuous-batching decode engine slots for LM :generate (0 = the
    # whole-request bucketed fallback) and on-device steps per host sync
    "decode_slots": 8,
    "decode_steps_per_sync": 4,
    # "" = single-chip; "tp=4" serves LMs tensor-parallel across the
    # pod's chips (params + KV cache sharded over the mesh)
    "serving_mesh": "",
    # version -> weight (e.g. {"v1": 90, "v2": 10}); empty = single version.
    # Renders one Deployment per version + an Istio VirtualService carrying
    # the weights (tf-serving-service-template.libsonnet trafficRule parity)
    "traffic_split": {},
    # request-logging http proxy sidecar service (k8s-model-server/http-proxy)
    "proxy": False,
    "proxy_port": 8008,
    # autoscaler service URL; non-empty wires the proxy's per-request
    # start/finish telemetry to it (kubeflow_tpu/autoscale), e.g.
    # "http://serving-autoscaler:8090"
    "autoscale_url": "",
}


def istio_virtual_service(name: str, ns: str, ports: List[int],
                          splits: Dict[str, int]) -> o.Obj:
    """Weighted version routing (reference: Istio VS weighting in
    ``tf-serving-service-template.libsonnet``; ``trafficRule`` "v1:100").

    One match-per-port http route so REST and gRPC each keep their own
    port while sharing the same version weights — a catch-all route would
    rewrite gRPC traffic onto the REST port.
    """
    total = sum(splits.values())
    if total != 100:
        raise ValueError(f"traffic_split weights must sum to 100, got {total}")
    for version, weight in splits.items():
        if not 0 <= int(weight) <= 100:
            raise ValueError(
                f"traffic_split weight for {version!r} must be in [0,100], "
                f"got {weight}")
    return {
        "apiVersion": "networking.istio.io/v1beta1",
        "kind": "VirtualService",
        "metadata": o.metadata(name, ns),
        "spec": {
            "hosts": [name],
            "http": [
                {
                    "match": [{"port": port}],
                    "route": [
                        {"destination": {"host": name,
                                         "subset": version,
                                         "port": {"number": port}},
                         "weight": weight}
                        for version, weight in sorted(splits.items())
                    ],
                }
                for port in ports
            ],
        },
    }


def istio_destination_rule(name: str, ns: str,
                           versions: List[str]) -> o.Obj:
    return {
        "apiVersion": "networking.istio.io/v1beta1",
        "kind": "DestinationRule",
        "metadata": o.metadata(name, ns),
        "spec": {
            "host": name,
            "subsets": [{"name": v, "labels": {"version": v}}
                        for v in sorted(versions)],
        },
    }


@register("serving", DEFAULTS,
          "JAX/XLA model server (replaces tf-serving / nvidia-inference-server)")
def render(config: DeploymentConfig, params: Dict[str, Any]) -> List[o.Obj]:
    ns = config.namespace
    name = params["name"]

    resources: Dict[str, Any] = {}
    if params["tpu_chips"]:
        resources = {"limits": {"google.com/tpu": params["tpu_chips"]}}

    env = {
        "KFTPU_MODEL_BASE_PATH": params["model_base_path"],
        "KFTPU_REST_PORT": str(params["rest_port"]),
        "KFTPU_GRPC_PORT": str(params["grpc_port"]),
        "KFTPU_BATCH_TIMEOUT_MS": str(params["batch_timeout_ms"]),
        "KFTPU_MAX_BATCH_SIZE": str(params["max_batch_size"]),
        "KFTPU_DECODE_SLOTS": str(params["decode_slots"]),
        "KFTPU_DECODE_STEPS_PER_SYNC": str(params["decode_steps_per_sync"]),
        # the compile cache rides the model volume, so a restarted pod
        # finds the executables its predecessor compiled
        "JAX_COMPILATION_CACHE_DIR":
            params["model_base_path"].rstrip("/") + "/.xla-compile-cache",
        **({"KFTPU_SERVING_MESH": params["serving_mesh"]}
           if params["serving_mesh"] else {}),
    }

    def version_deploy(version: str, pin: bool) -> o.Obj:
        labels = {"app": name, "version": version}
        # Under a traffic split, pin each backend to its own model version so
        # the Istio-weighted split actually routes between different models
        # (tf-serving runs one server per version dir for the same reason:
        # tf-serving-service-template.libsonnet per-version deployments).
        # Single-version serving stays unpinned: hot-reload of the latest
        # version is the advertised behavior there.
        pod = o.pod_spec([
            o.container(
                "server",
                params["image"],
                command=["python", "-m", "kubeflow_tpu.serving.server"],
                env={**env, "KFTPU_MODEL_VERSION": version} if pin else env,
                ports=[params["rest_port"], params["grpc_port"]],
                resources=resources,
            )
        ])
        return o.deployment(f"{name}-{version}", ns, pod,
                            replicas=params["replicas"], labels=labels)

    splits: Dict[str, int] = dict(params["traffic_split"] or {})
    versions = sorted(splits) if splits else [params["version"]]
    out: List[o.Obj] = [version_deploy(v, pin=bool(splits))
                        for v in versions]
    svc = o.service(
        name,
        ns,
        {"app": name},  # selects every version; Istio VS carries the weights
        [
            {"name": "rest", "port": params["rest_port"],
             "targetPort": params["rest_port"]},
            {"name": "grpc", "port": params["grpc_port"],
             "targetPort": params["grpc_port"]},
        ],
        labels={"app": name},
        annotations={
            "prometheus.io/scrape": "true",
            "prometheus.io/path": "/metrics",
            "prometheus.io/port": str(params["rest_port"]),
        },
    )
    out.append(svc)
    if splits:
        out.append(istio_destination_rule(name, ns, versions))
        out.append(istio_virtual_service(
            name, ns, [params["rest_port"], params["grpc_port"]], splits))
    if params["proxy"]:
        proxy_pod = o.pod_spec([
            o.container(
                "http-proxy",
                params["image"],
                command=["python", "-m", "kubeflow_tpu.serving.proxy"],
                env={"KFTPU_PROXY_PORT": str(params["proxy_port"]),
                     "KFTPU_BACKEND_URL":
                         f"http://{name}:{params['rest_port']}",
                     **({"KFTPU_AUTOSCALE_URL": params["autoscale_url"]}
                        if params["autoscale_url"] else {})},
                ports=[params["proxy_port"]],
            )
        ])
        out.append(o.deployment(f"{name}-proxy", ns, proxy_pod))
        out.append(o.service(
            f"{name}-proxy", ns, {"app": f"{name}-proxy"},
            [{"name": "http", "port": params["proxy_port"],
              "targetPort": params["proxy_port"]}]))
    return out
